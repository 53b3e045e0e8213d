"""DeepSeek-V3.2 on the serving path, CPU, at the tiny preset at which
every mechanism BINDS (index_topk 16, 16 experts in 4 groups of 4 with 2
groups a token and group 0 held, 1 dense + 1 expert layer + the MTP
module, sequences of 40-120): the grouped `noaux_tc` choice against plain
numpy, the two-row kernels against their XLA forms, the model's verify
rows and MTP rows through the cache, the accept rule's exactness, and
the speculating engine (greedy identity with and without speculation,
seeded replay under other batch neighbours, brownout to the plain step
and back, the counters on `/metrics`)."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bigdl_tpu.models import deepseek_v32
from bigdl_tpu.models.registry import get_family
from bigdl_tpu.observability.metrics import MetricsRegistry
from bigdl_tpu.ops import dsa, kvcache, moe_routed
from bigdl_tpu.ops.pallas import dsa_attention as kernels
from bigdl_tpu.serving.engine import EngineConfig, LLMEngine, SamplingParams
from bigdl_tpu.speculative import accept_and_resample

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "benchmark")]

CONFIG = "deepseek-v32-ep8-int4"


def _tiny_config():
    from harness import spec

    doc = json.loads(
        (ROOT / "benchmark" / "configs" / f"{CONFIG}.json").read_text())
    return spec.deep_update(doc, doc["tiny"])


@pytest.fixture(scope="module")
def model():
    from harness import weights_deepseek_v32 as weights

    return weights.build_model(_tiny_config(), 2 ** 31 + 3, merge=True)[0]


def test_registry_loads_the_family_and_the_mtp_block_is_a_layer_of_each_plane(
        model):
    cfg = model.config
    fam = get_family("DeepseekV32ForCausalLM")
    assert fam.name == "deepseek_v32" and fam.rewindable
    assert fam.speculative_depth(cfg) == 1
    assert (cfg.n_bodies, cfg.share, cfg.n_group, cfg.topk_group) == (
        3, (16, 0, 4), 4, 2)
    # the published YaRN: 192^-0.5 x (0.1 ln 40 + 1)^2 at the real widths
    real = deepseek_v32.DeepseekV32Config(rope_scaling=tuple(sorted({
        "type": "yarn", "factor": 40, "original_max_position_embeddings":
        4096, "beta_fast": 32, "beta_slow": 1, "mscale": 1,
        "mscale_all_dim": 1}.items())))
    assert real.scale == pytest.approx(192 ** -0.5 * 1.3689 ** 2, rel=1e-4)
    spec = kvcache.cache_spec_of(model.family, cfg)
    assert [(p.name, p.layers, p.dims) for p in spec.planes] == [
        ("latent", 3, (144,)), ("index", 3, (32,))]
    cache = kvcache.init_cache_spec(spec, 2, 64, per_slot_pos=True)
    assert {k: v.shape for k, v in cache.planes().items()} == {
        "latent": (3, 2, 144, 64), "index": (3, 2, 32, 64)}
    assert set(model.params["mtp"]) == {"enorm", "hnorm", "eh_proj",
                                        "shared_head_norm", "block"}
    # the expert stacks: one expert layer's and the MTP block's
    assert jax.tree.leaves(model.params["experts"])[0].shape[0] == 2


# -- the router --------------------------------------------------------------

def _numpy_grouped(logits, bias, top_k, n_group, topk_group, factor):
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    c = s + bias
    n, e = c.shape
    per = c.reshape(n, n_group, e // n_group)
    group = np.sort(per, axis=-1)[..., -2:].sum(-1)
    ids, ws = [], []
    for t in range(n):
        kept = np.argsort(-group[t], kind="stable")[:topk_group]
        masked = np.full(e, -np.inf)
        for g in kept:
            lo = g * (e // n_group)
            masked[lo:lo + e // n_group] = c[t, lo:lo + e // n_group]
        top = np.argsort(-masked, kind="stable")[:top_k]
        w = s[t, top]
        ids.append(top)
        ws.append(w / w.sum() * factor)
    return np.array(ids), np.array(ws)


@pytest.mark.parametrize("shape", [(8, 4, 3), (4, 2, 8), (8, 4, 8)],
                         ids=["8x32_keep4_top3", "4x16_keep2", "8x32_top8"])
def test_grouped_noaux_tc_is_the_published_choice(shape):
    """Groups scored by the sum of their two best biased scores, the
    best groups kept, the top-k among their experts by the biased score,
    weights the chosen experts' own scores renormalised times the
    factor: against plain numpy."""
    n_group, topk_group, top_k = shape
    e = n_group * (32 if n_group == 8 else 16)
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((40, e)).astype(np.float32)
    bias = (rng.standard_normal(e) * 0.3).astype(np.float32)
    ids, w = moe_routed.route(
        jnp.asarray(logits), top_k, n_group=n_group, topk_group=topk_group,
        method="noaux_tc", scaling_factor=2.5, norm_topk_prob=True,
        scoring="sigmoid", bias=jnp.asarray(bias))
    want_ids, want_w = _numpy_grouped(logits, bias, top_k, n_group,
                                      topk_group, 2.5)
    assert (np.sort(np.asarray(ids), axis=1)
            == np.sort(want_ids, axis=1)).all()
    order = np.argsort(np.asarray(ids), axis=1)
    worder = np.argsort(want_ids, axis=1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, 1),
        np.take_along_axis(want_w, worder, 1), rtol=2e-5)
    # the group limit binds: plain top-k differs on some token
    plain, _ = moe_routed.route(
        jnp.asarray(logits), top_k, method="noaux_tc", norm_topk_prob=True,
        scoring="sigmoid", bias=jnp.asarray(bias))
    assert (np.sort(np.asarray(plain), 1) != np.sort(want_ids, 1)).any()


def test_one_group_noaux_tc_is_bit_for_bit_what_it_was():
    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(64) * 0.1, jnp.float32)
    ids, w = moe_routed.route(logits, 6, method="noaux_tc",
                              scaling_factor=1.5, norm_topk_prob=True,
                              scoring="sigmoid", bias=bias)
    scores = jax.nn.sigmoid(logits)
    _, topi = jax.lax.top_k(scores + bias, 6)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20) * 1.5
    assert (np.asarray(ids) == np.asarray(topi)).all()
    assert (np.asarray(w) == np.asarray(topv)).all()


# -- the kernels: R = 1 as before, R = 2 folded beside the heads -------------

def _planes(rng, b, s, c, r, di, layers=2):
    lat = jnp.asarray(rng.standard_normal((layers, b, c + r, s)),
                      jnp.bfloat16)
    idx = jnp.asarray(rng.standard_normal((layers, b, di, s)), jnp.bfloat16)
    return lat, idx


@pytest.mark.parametrize("rows", [1, 2])
def test_index_score_kernel_matches_xla_per_row(rows):
    rng = np.random.default_rng(5)
    b, s, hi, di = 3, 256, 4, 32
    _, idx = _planes(rng, b, s, 128, 16, di)
    pos = jnp.asarray([5, 130, 254], jnp.int32)
    lead = (b, rows) if rows > 1 else (b,)
    q = jnp.asarray(rng.standard_normal(lead + (hi, di)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal(lead + (hi,)), jnp.float32)
    got = dsa.dsa_index_scores_decode(q, w, idx, 1, pos, backend="pallas")
    want = dsa.dsa_index_scores_decode(q, w, idx, 1, pos, backend="xla")
    assert got.shape == lead + (s,)
    live = np.isfinite(np.asarray(want))
    assert (np.isfinite(np.asarray(got)) == live).all()
    # row r of a slot is live up to pos + r
    at = np.asarray(pos)[:, None] + np.arange(max(rows, 1))[None]
    assert (live.reshape(b, -1, s).sum(-1) == at + 1).all()
    np.testing.assert_allclose(np.where(live, np.asarray(got), 0),
                               np.where(live, np.asarray(want), 0),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("rows", [1, 2])
def test_select_and_sparse_sweep_match_xla_per_row(rows):
    rng = np.random.default_rng(6)
    b, s, h, c, r = 3, 256, 4, 128, 16
    lat, _ = _planes(rng, b, s, c, r, 32)
    pos = jnp.asarray([9, 127, 200], jnp.int32)
    lead = (b, rows) if rows > 1 else (b,)
    scores = jnp.asarray(rng.standard_normal(lead + (s,)), jnp.float32)
    at = (pos[:, None] + jnp.arange(rows)[None]).reshape(lead)
    scores = jnp.where(jnp.arange(s) <= at[..., None], scores, -jnp.inf)
    sel_k = dsa.dsa_select_decode(scores, 16, backend="pallas") != 0
    sel_x = dsa.dsa_select_decode(scores, 16, backend="xla") != 0
    assert (np.asarray(sel_k) == np.asarray(sel_x)).all()
    assert (np.asarray(sel_x).sum(-1) == np.minimum(np.asarray(at) + 1,
                                                    16)).all()
    qc = jnp.asarray(rng.standard_normal(lead + (h, c)), jnp.bfloat16)
    qp = jnp.asarray(rng.standard_normal(lead + (h, r)), jnp.bfloat16)
    got = dsa.sparse_mla_decode(qc, qp, lat, 1, pos, sel_x, 0.08,
                                backend="pallas")
    want = dsa.sparse_mla_decode(qc, qp, lat, 1, pos, sel_x, 0.08,
                                 backend="xla")
    assert got.shape == lead + (h, c)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
    if rows == 2:
        # the folded rows are the one-row kernel's rows, each at its own
        # position with its own selection
        for i in range(2):
            one = dsa.sparse_mla_decode(qc[:, i], qp[:, i], lat, 1, pos + i,
                                        sel_x[:, i], 0.08, backend="pallas")
            np.testing.assert_allclose(np.asarray(got[:, i], np.float32),
                                       np.asarray(one, np.float32),
                                       atol=2e-2)


def test_mla_decode_attention_is_untouched_by_the_two_row_forms():
    from bigdl_tpu.ops.pallas import mla_attention

    rng = np.random.default_rng(7)
    lat, _ = _planes(rng, 2, 256, 128, 16, 32)
    pos = jnp.asarray([17, 255], jnp.int32)
    qc = jnp.asarray(rng.standard_normal((2, 4, 128)), jnp.bfloat16)
    qp = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.bfloat16)
    got = mla_attention.mla_decode_attention(qc, qp, lat, 0, pos, 0.08,
                                             backend="pallas")
    want = mla_attention.mla_decode_attention(qc, qp, lat, 0, pos, 0.08,
                                              backend="xla")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)
    assert kernels.SPARSE_NAME == "sparse_mla_decode"
    assert kernels.INDEX_NAME == "dsa_index_score"


# -- the model through the cache against ONE float32 pass of the reference ---

@pytest.fixture(scope="module")
def walked(model):
    """Chunks of 32 (the MTP block lagged by one, the carry across the
    chunk edge), `mtp_forward` for the last prompt row, then verify steps
    of two rows with the MTP module over both: every logit the engine's
    programs compute, beside one pass of the reference."""
    from harness import (reference_deepseek_v32 as reference,
                         weights_deepseek_v32 as weights)

    config = _tiny_config()
    cfg, params = model.config, model.params
    canonical = weights.canonical_params(config, 2 ** 31 + 3, check=False)
    ids = np.random.default_rng(8).integers(1, 256, 74)
    arch = config["reference"]
    quant = {"qtype": "sym_int4", "block": 32}
    hid = reference.hidden_states(canonical, arch, quant, ids.tolist())
    ref_main = np.asarray(reference._head(canonical, arch, quant, hid,
                                          canonical["norm"]))
    ref_mtp = np.asarray(reference.mtp_logits(canonical, arch, quant,
                                              ids.tolist(), hid))
    fwd = jax.jit(deepseek_v32.forward_hidden, static_argnums=1)
    mtp = jax.jit(deepseek_v32.mtp_forward, static_argnums=1)
    cache = deepseek_v32.new_cache(cfg, 1, 128)
    carry = jnp.zeros((1, cfg.hidden_size), jnp.bfloat16)
    main, hidden = [], []
    for a in (0, 32):
        lg, h, cache = fwd(params, cfg, jnp.asarray(ids[None, a:a + 32]),
                           cache, carry)
        carry = h[:, -1]
        main.append(np.asarray(lg[0]))
        hidden.append(h)
    h_all = jnp.concatenate(hidden, axis=1)
    # the last prompt row's MTP row waits for the next token
    mt = {}
    lg, cache = mtp(params, cfg, h_all[:, 63:64],
                    jnp.asarray(ids[None, 64:65]),
                    cache, jnp.int32(63))
    mt[63] = np.asarray(lg[0, 0])
    for t in range(64, 72, 2):
        lg, h, cache = fwd(params, cfg, jnp.asarray(ids[None, t:t + 2]), cache)
        main.append(np.asarray(lg[0]))
        ml, cache = mtp(params, cfg, h, jnp.asarray(ids[None, t + 1:t + 3]),
                        cache, jnp.int32(t))
        mt[t], mt[t + 1] = np.asarray(ml[0, 0]), np.asarray(ml[0, 1])
    # the prefill-written MTP rows, read back through one more row of
    # the module at position 62 (it attends rows 0 .. 62)
    return dict(main=np.concatenate(main), mtp=mt, ref_main=ref_main,
                ref_mtp=ref_mtp, cache=cache, reference=reference,
                layers=arch["layers"])


@pytest.mark.parametrize("rows", ["prefill_under_topk", "prefill_past_topk",
                                  "across_the_chunk_edge", "verify_row_0",
                                  "verify_row_1"])
def test_main_logits_of_chunks_and_of_both_verify_rows(walked, rows):
    sl = {"prefill_under_topk": slice(0, 16),
          "prefill_past_topk": slice(16, 64),
          "across_the_chunk_edge": slice(30, 34),
          "verify_row_0": slice(64, 72, 2),
          "verify_row_1": slice(65, 72, 2)}[rows]
    rel_l2 = walked["reference"].relative_l2
    got, want = walked["main"][sl], walked["ref_main"][sl]
    if rows == "prefill_under_topk":
        # under index_topk no coin falls: the bfloat16 walk
        assert rel_l2(got, want) < walked["reference"].rounding_walk(
            walked["layers"])
        return
    # past it one of 16 selected positions may swap on a near tie, a
    # sixteenth of a row's softmax mass: most rows stay close, none is a
    # stranger's (unrelated logits read 1.41)
    per_row = [rel_l2(g, w) for g, w in zip(got, want)]
    assert np.median(per_row) < 0.2 and max(per_row) < 0.8, per_row


@pytest.mark.parametrize("at", [63, 64, 65, 70, 71])
def test_mtp_logits_at_prefill_written_and_verify_written_rows(walked, at):
    """Row `at` of the module (from hidden row `at` and token `at + 1`)
    attends ITS rows `0 .. at`: those the lagged prefill pass wrote (0 ..
    62, across the chunk edge), the admission row (63) and the verify
    steps' rows."""
    rel = walked["reference"].relative_l2(walked["mtp"][at],
                                          walked["ref_mtp"][at])
    assert rel < 0.8, rel


def test_mtp_logits_stay_close_on_most_rows(walked):
    rows = [walked["reference"].relative_l2(walked["mtp"][t],
                                            walked["ref_mtp"][t])
            for t in sorted(walked["mtp"])]
    assert np.median(rows) < 0.2, rows


def test_the_cache_holds_pos_rows_of_every_body(walked):
    cache = walked["cache"]
    assert int(cache.pos) == 72
    lat = np.asarray(cache.latent, np.float32)
    assert lat.shape[0] == 3
    # every body wrote rows 0 .. 71 (the MTP block too) and none past
    assert (np.abs(lat[:, 0, :, :72]).sum(axis=1) > 0).all()
    assert (lat[:, 0, :, 72:] == 0).all()


# -- the accept rule ---------------------------------------------------------

def _pq(seed, v=16):
    rng = np.random.default_rng(seed)
    p = rng.dirichlet(np.ones(v) * 0.5, size=2).astype(np.float32)
    q = rng.dirichlet(np.ones(v) * 0.5).astype(np.float32)
    return p, q


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_accept_and_resample_reproduces_p_exactly(seed):
    """Over every draft d of a 16-id vocabulary: P(out = x) = q(x) a_x +
    (sum_d q(d) (1 - a_d)) resid(x) with the function's own accept
    threshold and residual, is p(x)."""
    p, q = _pq(seed)
    v = p.shape[-1]
    drafts = jnp.arange(v, dtype=jnp.int32)[:, None]
    pb = jnp.broadcast_to(jnp.asarray(p), (v, 2, v))
    qb = jnp.broadcast_to(jnp.asarray(q), (v, 1, v))
    a = np.minimum(1.0, p[0] / q)
    # the threshold is the function's: accepted just under a_d, rejected
    # just over
    lo, _ = accept_and_resample(pb, qb, drafts,
                                jnp.asarray(a * 0.999)[:, None])
    hi, resid = accept_and_resample(
        pb, qb, drafts, jnp.asarray(np.minimum(a * 1.001 + 1e-6, 2.0))[:, None])
    assert (np.asarray(lo) == 1).all()
    assert (np.asarray(hi)[a < 0.999] == 0).all()
    _, bonus = accept_and_resample(pb, qb, drafts, jnp.zeros((v, 1)))
    np.testing.assert_allclose(np.asarray(bonus), np.tile(p[1], (v, 1)),
                               rtol=1e-6)
    rejected = np.asarray(hi) == 0
    r = np.asarray(resid)[rejected][0]
    np.testing.assert_allclose(r, np.maximum(p[0] - q, 0)
                               / np.maximum(p[0] - q, 0).sum(), rtol=1e-5)
    out = q * a + (q * (1 - a)).sum() * r
    np.testing.assert_allclose(out, p[0], atol=2e-6)
    # no draft (valid False): the target's own distribution
    n, dist = accept_and_resample(pb, jnp.zeros_like(qb), drafts,
                                  jnp.zeros((v, 1)),
                                  jnp.zeros((v, 1), bool))
    assert (np.asarray(n) == 0).all()
    np.testing.assert_allclose(np.asarray(dist), np.tile(p[0], (v, 1)),
                               rtol=1e-6)


def test_accept_and_resample_draws_p_over_a_seeded_count():
    p, q = _pq(5)
    n = 40000
    key = jax.random.PRNGKey(11)
    kd, ku, kr = jax.random.split(key, 3)
    drafts = jax.random.categorical(kd, jnp.log(jnp.asarray(q)), shape=(n, 1))
    n_acc, dist = accept_and_resample(
        jnp.broadcast_to(jnp.asarray(p), (n, 2, 16)),
        jnp.broadcast_to(jnp.asarray(q), (n, 1, 16)),
        drafts.astype(jnp.int32), jax.random.uniform(ku, (n, 1)))
    fix = jax.random.categorical(kr, jnp.log(jnp.maximum(dist, 1e-30)))
    first = np.where(np.asarray(n_acc) == 1, np.asarray(drafts[:, 0]),
                     np.asarray(fix))
    freq = np.bincount(first, minlength=16) / n
    assert np.abs(freq - p[0]).max() < 0.01
    share = float(np.mean(np.asarray(n_acc)))
    assert abs(share - np.minimum(p[0], q).sum()) < 0.01


# -- the speculating engine --------------------------------------------------

def _engine(model, spec, **kw):
    return LLMEngine(model, EngineConfig(
        max_batch=kw.pop("max_batch", 8), max_seq=256, prefill_chunk=32,
        speculative_tokens=spec, sentinel=False, quality=False, **kw),
        registry=MetricsRegistry())


def _prompts(n, seed=1):
    rng = np.random.default_rng(seed)
    return [list(map(int, rng.integers(1, 256, k)))
            for k in rng.integers(33, 110, n)]


def _agreeing(model):
    """The tiny model with every block's output zeroed and `eh_proj` the
    identity on its embedding half: the main stack's logits are a
    function of the last token alone and the MTP module computes the
    same function of the NEXT token, so every greedy draft is the main
    model's argmax and is accepted."""
    zero = lambda t: jax.tree.map(jnp.zeros_like, t)       # noqa: E731
    params = dict(model.params)

    def dead(lp):
        lp = dict(lp)
        for k in ("o_proj", "down_proj", "shared_down"):
            if k in lp:
                lp[k] = zero(lp[k])
        return lp

    params["layers"] = tuple(dead(lp) for lp in params["layers"])
    params["experts"] = dict(params["experts"],
                             experts_down=zero(params["experts"]
                                               ["experts_down"]))
    d = model.config.hidden_size
    params["mtp"] = dict(
        params["mtp"], block=dead(params["mtp"]["block"]),
        eh_proj=jnp.concatenate([jnp.eye(d), jnp.zeros((d, d))]).astype(
            jnp.bfloat16))

    class Agreeing:
        config, family, hf_config = (model.config, model.family,
                                     getattr(model, "hf_config", None))
        qtype = "sym_int4"

    Agreeing.params = params
    return Agreeing


@pytest.mark.parametrize("which", ["rejecting", "accepting"])
def test_greedy_stream_with_speculation_is_the_stream_without(model, which):
    """8 slots, 11 requests of mixed lengths, `max_tokens` odd and even
    (it lands on the first of two tokens of an accepted pair) and a stop
    token: token for token the stream of `speculative_tokens` 0. The
    seeded model rejects nearly every greedy draft; the agreeing model
    accepts every one."""
    m = model if which == "rejecting" else _agreeing(model)
    prompts = _prompts(11)
    outs = {}
    for spec in (0, 1):
        eng = _engine(m, spec)
        for i, p in enumerate(prompts):
            eng.add_request(f"r{i}", p, SamplingParams(
                temperature=0.0, max_tokens=6 + i, ignore_eos=True))
        got = {f"r{i}": [] for i in range(len(prompts))}
        done = set()
        while len(done) < len(prompts):
            eng.step()
            for rid in got:
                for o in eng.get_outputs(rid):
                    got[rid].extend(o.new_token_ids)
                    if o.finished:
                        done.add(rid)
        outs[spec] = got
        if spec:
            text = eng.registry.render()
            acc = _counter(text, 'bigdl_tpu_mtp_drafts_total'
                                 '{outcome="accepted"}')
            rej = _counter(text, 'bigdl_tpu_mtp_drafts_total'
                                 '{outcome="rejected"}')
            if which == "accepting":
                assert acc > 20 and rej == 0
            else:
                assert rej > 20 and acc <= 2
    assert outs[0] == outs[1]
    assert [len(v) for v in outs[1].values()] == [6 + i for i in range(11)]
    if which == "accepting":
        # a stop token that the stream holds at an odd place ends the
        # request there: the second token of its pair is dropped
        stream = outs[0]["r10"]
        stop = stream[3]
        cut = stream.index(stop)
        for spec in (0, 1):
            eng = _engine(m, spec)
            (got,) = eng.generate([prompts[10]], SamplingParams(
                temperature=0.0, max_tokens=16, stop_token_ids=[stop]))
            assert got == stream[:cut + 1]


def _compiled(text):
    """Names of the tracked programs an engine's own registry saw
    compile."""
    import re

    return set(re.findall(r'bigdl_tpu_jit_compiles_total\{fn="([^"]+)"\}',
                          text))


def _counter(text, series):
    for line in text.splitlines():
        if line.startswith(series + " "):
            return float(line.split()[-1])
    raise AssertionError(f"{series} not on /metrics")


def _sampled(eng, prompts, seeds):
    for i, (p, sd) in enumerate(zip(prompts, seeds)):
        eng.add_request(f"s{i}", p, SamplingParams(
            temperature=1.0, top_k=0, max_tokens=14, seed=sd,
            ignore_eos=True))
    got = {f"s{i}": [] for i in range(len(prompts))}
    done = set()
    while len(done) < len(prompts):
        eng.step()
        for rid in got:
            for o in eng.get_outputs(rid):
                got[rid].extend(o.new_token_ids)
                if o.finished:
                    done.add(rid)
    return got


def test_seeded_sampled_stream_replays_under_other_batch_neighbours(model):
    """Uniforms, gumbels and the draft's draw are keyed by (slot seed,
    absolute token index): the request alone, and the same request among
    five others in another slot, stream the same tokens; both branches
    (accept with a bonus token, reject with a resample) run."""
    prompts = _prompts(6, seed=4)
    alone = _sampled(_engine(model, 1), prompts[:1], [77])
    eng = _engine(model, 1)
    # the neighbours first, so that the request lands in another slot
    crowd = _sampled(eng, prompts[1:] + prompts[:1], [1, 2, 3, 4, 5, 77])
    assert crowd["s5"] == alone["s0"] and len(alone["s0"]) == 14
    text = eng.registry.render()
    assert _counter(text, 'bigdl_tpu_mtp_drafts_total{outcome="accepted"}') > 0
    assert _counter(text, 'bigdl_tpu_mtp_drafts_total{outcome="rejected"}') > 0
    assert _counter(text, 'bigdl_tpu_spec_accept_ratio_count{mode="mtp"}') > 0


def test_brownout_falls_to_the_plain_step_and_back(model):
    """Brownout level 1 (`speculative_allowed` False) takes the plain
    one-row step and `engine_mtp_row` keeps the module's rows and the
    standing draft current; back at level 0 the verify step goes on. The
    greedy stream is the stream of `speculative_tokens` 0, and the MTP
    block's cache rows are those of an undisturbed speculating run."""
    prompts = _prompts(3, seed=9)
    sp = SamplingParams(temperature=0.0, max_tokens=18, ignore_eos=True)
    want = _engine(model, 0, max_batch=4).generate(prompts, sp)

    def run(brown):
        eng = _engine(model, 1, max_batch=4)
        for i, p in enumerate(prompts):
            eng.add_request(f"b{i}", p, sp)
        got = {f"b{i}": [] for i in range(3)}
        n, lat = 0, None
        while eng.has_unfinished():
            if brown:
                eng.overload.level = 1 if 6 <= n < 12 else 0
            eng.step()
            n += 1
            for rid in got:
                for o in eng.get_outputs(rid):
                    got[rid].extend(o.new_token_ids)
            if max(len(v) for v in got.values()) >= 16 and lat is None:
                # rows by the host's count: the device may be a step
                # further on, under a step that went out ahead
                lat = (np.asarray(eng.cache.latent[-1], np.float32),
                       [len(s.req.prompt_token_ids) + len(s.generated) - 1
                        for s in eng.slots[:3]])
        return eng, [got[f"b{i}"] for i in range(3)], lat

    eng, got, lat = run(True)
    assert got == want
    text = eng.registry.render()
    assert _counter(text, 'bigdl_tpu_mtp_slot_steps_total{kind="plain"}') >= 6
    assert _counter(text, 'bigdl_tpu_mtp_slot_steps_total{kind="verify"}') > 6
    assert "engine_mtp_row" in _compiled(text)
    assert "engine_decode_hidden" in _compiled(text)
    _, got2, lat2 = run(False)
    assert got2 == want
    (rows, pos), (rows2, pos2) = lat, lat2
    for b in range(3):
        n = min(pos[b], pos2[b])
        assert n > len(prompts[b])
        np.testing.assert_allclose(rows[b, :, :n], rows2[b, :, :n],
                                   atol=0.05)


def test_speculation_is_refused_where_the_family_has_no_module():
    from bigdl_tpu.utils.testing import tiny_random_model

    m = tiny_random_model()
    with pytest.raises(ValueError, match="speculative_tokens"):
        LLMEngine(m, EngineConfig(max_batch=2, max_seq=64,
                                  speculative_tokens=1))
    eng = LLMEngine(m, EngineConfig(max_batch=2, max_seq=64, sentinel=False,
                                    quality=False),
                    registry=MetricsRegistry())
    eng.generate([[1, 2, 3, 4]], SamplingParams(max_tokens=3))
    text = eng.registry.render()
    # a family without a module: no MTP counter, no MTP program
    assert "bigdl_tpu_mtp_" not in text
    assert 'mode="mtp"' not in text
    compiled = _compiled(text)
    assert not [k for k in compiled if "mtp" in k or "hidden" in k]
    assert "engine_decode_resident" in compiled


def test_metrics_of_an_mtp_engine_render_from_scrape_one(model):
    eng = _engine(model, 1, max_batch=2)
    text = eng.registry.render()
    for series in ('bigdl_tpu_mtp_drafts_total{outcome="accepted"} 0',
                   'bigdl_tpu_mtp_drafts_total{outcome="rejected"} 0',
                   'bigdl_tpu_mtp_slot_steps_total{kind="verify"} 0',
                   'bigdl_tpu_mtp_slot_steps_total{kind="plain"} 0'):
        assert series in text, series
    eng.generate(_prompts(2), SamplingParams(max_tokens=5))
    text = eng.registry.render()
    compiled = _compiled(text)
    assert {"engine_decode_resident_mtp", "engine_prefill_mtp",
            "engine_mtp_row"} <= compiled
    assert "engine_decode_resident" not in compiled
    assert "engine_prefill" not in compiled
    verify = _counter(text, 'bigdl_tpu_mtp_slot_steps_total{kind="verify"}')
    tokens = _counter(text, "bigdl_tpu_tokens_generated_total")
    assert verify > 0 and tokens == 10
    # the DSA position counters count both rows of a verify step
    live = _counter(text, 'bigdl_tpu_dsa_positions_total{kind="live"}')
    assert live > 2 * 3 * 33 * verify / 2
