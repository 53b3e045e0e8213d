"""The plain reference of DeepSeek-V3.2-Exp: a forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, one layer at a time, NOT
absorbed (K and V are materialised per head from the compressed rows),
no kernel, no cache, no batching, no speculation, on weights dequantized
by plain arithmetic. No import of the program. The pieces that are the
same arithmetic as another configuration's reference (the exact
selection, row blocking, YaRN's frequencies) are imported from that
reference's module.

The layer, told by the ``reference`` block of the configuration's file
(``arch``; pre-norm residual, RMSNorm, untied head; ``x`` the normed
input), EVERY layer alike:

- ``c_q = RMSNorm(x W_qa)``, ``[q_n | q_r]_h = c_q W_qb``, ``[c_kv | k_r] =
  x W_kva``, ``c_kv <- RMSNorm(c_kv)``, rope on ``q_r`` and the one shared
  ``k_r`` (channels 2i and 2i+1 together, YaRN's frequencies), ``[k_n |
  v]_h = c_kv W_kvb``;
- the indexer (``arch["index"]``): ``q_I = c_q W_Iq``, ``k_I = LayerNorm(x
  W_Ik)`` (weight and bias, eps 1e-6), ``w = x W_Iw heads^-1/2 dim^-1/2``,
  rope on the first ``rope`` channels of both, rotated as two HALVES (i
  with i + rope / 2); ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``;
  ``S_t`` = the ``topk`` positions ``s <= t`` of largest ``I[t, .]``;
- ``softmax over S_t of (q_n . k_n + q_r . k_r) (nope + rope)^-1/2 m^2``,
  ``m = 0.1 mscale_all_dim ln(factor) + 1``, times ``v``; then ``W_o``;
- feed-forward: the first ``first_k_dense`` layers dense SwiGLU, the
  others the shared expert plus ``sum_i w_i SwiGLU^(e_i)(x)``: ``s =
  sigmoid(x W_r)`` in float32, ``c = s + b``, a group (``experts_total /
  n_group`` consecutive experts) scores the sum of its two largest ``c``,
  the ``topk_group`` best groups stay, the ``experts_per_tok`` largest
  ``c`` among them are chosen, weights ``s[chosen] / sum`` times
  ``routed_scaling_factor``;
- the MTP module (``params["mtp"]``): with ``h_i`` the stack's output at
  position i before the final norm, ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(h_i)] W_eh``, one block of the expert kind over the positions
  ``0 .. S - 2`` (its own attention over ITS rows), then
  ``Head(RMSNorm_s(.))``: row i is the distribution of token i + 2.

Departures from the published model: weights are the seeded random
block-quantized planes the program serves, dequantized here as ``(code -
8) * scale``; the configuration's SHARE: of the chosen experts only those
this chip holds add to the sum, in the program and here alike; index
keys in float32 here (bf16 in the program; fp8 published) and not
Hadamard-rotated.

``alter`` plants a fault or a lower precision for the controls of
``checks_deepseek_v32``: ``group_limit: False`` (plain top 8 of 256),
``group_score: "max"``, ``eh_swap: True``, ``hnorm: False``,
``blind_rows`` (rows that do not see the position before them),
``latent_dtype``; ``given`` hands a layer's attention the selection of
someone else.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm)
from harness.reference_deepseek_v2 import yarn_inv_freq, yarn_mscale
from harness.reference_dots3_note import (INDEX_NORM_EPS, _causal_groups,
                                          _layer_norm, _row_blocks, _swiglu,
                                          expert_capacity, select)

HEAD_GROUP = 8        # heads whose [rows, S] scores are live together


def _inv_freq(arch):
    a = arch["attn"]
    y = a["yarn"]
    return yarn_inv_freq({
        "theta": a["theta"], "factor": y["factor"],
        "original_max_position_embeddings": y["original"],
        "beta_fast": y["beta_fast"], "beta_slow": y["beta_slow"]},
        int(a["rope"]))


def softmax_scale(arch) -> float:
    a = arch["attn"]
    y = a["yarn"]
    m = yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
    return (int(a["nope"]) + int(a["rope"])) ** -0.5 * m * m


def _rope_pairs(x, positions, inv):
    """x ``[S, H, rd]``: channels 2i and 2i+1 rotate together."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _rope_halves(x, positions, inv, rd: int):
    """x ``[S, H, d]``: of the first ``rd`` channels, i rotates with
    ``i + rd / 2``."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b, rest = x[..., :rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def index_scores(y, c_q, lp, arch, quant):
    """``I`` ``[S, S]`` float32, ``-inf`` above the diagonal."""
    import jax
    import jax.numpy as jnp

    ix = arch["index"]
    hi, di, rd = int(ix["heads"]), int(ix["dim"]), int(ix["rope"])
    inv = _inv_freq(arch)
    s = y.shape[0]
    pos = jnp.arange(s)
    q_i = _rope_halves((c_q @ _dense(lp["index_q_proj"], quant)).reshape(
        s, hi, di), pos, inv, rd)
    k_i = _layer_norm(y @ _dense(lp["index_k_proj"], quant),
                      lp["index_k_norm"], lp["index_k_norm_bias"],
                      INDEX_NORM_EPS)
    k_i = _rope_halves(k_i[:, None, :], pos, inv, rd)[:, 0]        # [S, di]
    w = (y @ _dense(lp["index_w_proj"], quant)) * (hi ** -0.5 * di ** -0.5)
    g = math.gcd(hi, HEAD_GROUP)
    rb = _row_blocks(s)
    runs = []
    for lo, past in _causal_groups(s // rb):
        keys = k_i[:past * rb]              # none past the run's last row

        def heads(args, keys=keys):
            qg, wg = args                               # [S, g, di], [S, g]
            return jnp.einsum("sg,sgt->st", wg, jax.nn.relu(
                jnp.einsum("sgd,td->sgt", qg, keys)))

        def rows(args, heads=heads):
            qb, wb = args                               # [rb, hi, .]
            parts = jax.lax.map(heads, (
                jnp.moveaxis(qb.reshape(rb, hi // g, g, di), 1, 0),
                jnp.moveaxis(wb.reshape(rb, hi // g, g), 1, 0)))
            return parts.sum(axis=0)

        run = jax.lax.map(rows, (
            q_i[lo * rb:past * rb].reshape(past - lo, rb, hi, di),
            w[lo * rb:past * rb].reshape(past - lo, rb, hi)))
        runs.append(jnp.pad(run.reshape(-1, past * rb),
                            ((0, 0), (0, s - past * rb))))
    tot = jnp.concatenate(runs)
    return jnp.where(pos[None, :] <= pos[:, None], tot, -jnp.inf)


def cache_rows(y, lp, arch, quant, positions=None):
    """What a cache keeps of the normed rows ``y`` ``[S, D]`` at
    ``positions`` (``0 .. S - 1`` where not given): the latent rows ``[S,
    C + R]`` (the normed ``c_kv`` and the roped ``k_r``) and the index
    keys ``[S, Di]``."""
    import jax.numpy as jnp

    a, ix = arch["attn"], arch["index"]
    c, r = int(a["kv_lora_rank"]), int(a["rope"])
    eps = float(arch["norm_eps"])
    pos = jnp.arange(y.shape[0]) if positions is None else positions
    inv = _inv_freq(arch)
    kv = y @ _dense(lp["kv_a_proj"], quant)
    c_kv = _rms_norm(kv[:, :c], lp["kv_a_layernorm"], eps)
    k_pe = _rope_pairs(kv[:, None, c:c + r], pos, inv)[:, 0]
    k_i = _layer_norm(y @ _dense(lp["index_k_proj"], quant),
                      lp["index_k_norm"], lp["index_k_norm_bias"],
                      INDEX_NORM_EPS)
    k_i = _rope_halves(k_i[:, None, :], pos, inv, int(ix["rope"]))[:, 0]
    return jnp.concatenate([c_kv, k_pe], axis=-1), k_i


def attention(y, lp, arch, quant, alter=None, given=None,
              probe: Optional[dict] = None):
    """One layer's attention on the normed ``y`` ``[S, D]``, K and V per
    head. ``given``: a ``[S, S]`` selection to use in the place of this
    layer's own; ``probe``: a dict that receives ``index_scores`` and
    ``selected``."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    a = arch["attn"]
    h, c = int(a["heads"]), int(a["kv_lora_rank"])
    nope, r, vd = int(a["nope"]), int(a["rope"]), int(a["v"])
    eps = float(arch["norm_eps"])
    s = y.shape[0]
    pos = jnp.arange(s)
    inv = _inv_freq(arch)
    c_q = _rms_norm(y @ _dense(lp["q_a_proj"], quant), lp["q_a_layernorm"],
                    eps)
    kv = y @ _dense(lp["kv_a_proj"], quant)
    c_kv = _rms_norm(kv[:, :c], lp["kv_a_layernorm"], eps)
    k_pe = _rope_pairs(kv[:, None, c:c + r], pos, inv)[:, 0]       # [S, r]
    if alter.get("latent_dtype") is not None:
        c_kv = c_kv.astype(alter["latent_dtype"]).astype(jnp.float32)
        k_pe = k_pe.astype(alter["latent_dtype"]).astype(jnp.float32)
    scale = softmax_scale(arch)
    if given is not None:
        allowed = given
        for t in alter.get("blind_rows", ()):
            allowed = allowed.at[t, t - 1].set(False)
    else:
        scores = index_scores(y, c_q, lp, arch, quant)
        for t in alter.get("blind_rows", ()):   # row t does not see t - 1
            scores = scores.at[t, t - 1].set(-jnp.inf)
        allowed = select(scores, int(arch["index"]["topk"]))
        if probe is not None:
            probe["index_scores"], probe["selected"] = scores, allowed

    g = math.gcd(h, HEAD_GROUP)
    rb = _row_blocks(s)
    ok_blocks = allowed.reshape(s // rb, rb, s)
    groups = _causal_groups(s // rb)

    def heads(args):
        """One group of heads: its queries, keys and values from the two
        latents, its rows in blocks."""
        w_qb, w_kvb = args         # [q_lora, g, nope + r], [c, g, nope + vd]
        q = jnp.einsum("sq,qgd->sgd", c_q, w_qb)
        q_pe = _rope_pairs(q[..., nope:], pos, inv)
        kvb = jnp.einsum("sc,cgd->sgd", c_kv, w_kvb)
        runs = []
        for lo, past in groups:
            ext = past * rb              # causal: no key past the run
            kn, kp, vv = kvb[:ext, :, :nope], k_pe[:ext], kvb[:ext, :, nope:]

            def rows(rargs, kn=kn, kp=kp, vv=vv):
                qn, qp, ok = rargs
                sc = (jnp.einsum("sgd,tgd->gst", qn, kn)
                      + jnp.einsum("sgr,tr->gst", qp, kp)) * scale
                probs = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf),
                                       axis=-1)
                return jnp.einsum("gst,tgd->sgd", probs, vv)

            runs.append(jax.lax.map(rows, (
                q[lo * rb:ext, :, :nope].reshape(past - lo, rb, g, nope),
                q_pe[lo * rb:ext].reshape(past - lo, rb, g, r),
                ok_blocks[lo:past, :, :ext])))
        return jnp.concatenate(runs).reshape(s, g, vd)

    def by_group(w, width):
        w = _dense(w, quant)
        return jnp.moveaxis(w.reshape(w.shape[0], h // g, g, width), 1, 0)

    out = jax.lax.map(heads, (by_group(lp["q_b_proj"], nope + r),
                              by_group(lp["kv_b_proj"], nope + vd)))
    o = jnp.moveaxis(out, 0, 1).reshape(s, h * vd)
    return o @ _dense(lp["o_proj"], quant)


def route(scores, bias, arch: Dict[str, Any], alter=None):
    """Sigmoid scores ``[S, E]`` -> weights ``[S, E]`` float32: the
    routing weight of each expert for each token, 0 where not chosen.
    The bias chooses and does not weigh."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    s, e = scores.shape
    k = int(arch["experts_per_tok"])
    ng, tg = int(arch.get("n_group", 1)), int(arch.get("topk_group", 1))
    choice = scores + bias.astype(jnp.float32)
    if ng > 1 and alter.get("group_limit", True):
        per = choice.reshape(s, ng, e // ng)
        if alter.get("group_score", "top2") == "max":
            group = per.max(axis=-1)
        else:
            group = jax.lax.top_k(per, 2)[0].sum(axis=-1)
        _, gi = jax.lax.top_k(group, tg)
        keep = jnp.zeros((s, ng), bool).at[jnp.arange(s)[:, None],
                                          gi].set(True)
        choice = jnp.where(jnp.repeat(keep, e // ng, axis=1), choice,
                           -jnp.inf)
    _, topi = jax.lax.top_k(choice, k)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if arch.get("norm_topk_prob", True) and k > 1:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    topv = topv * float(arch.get("routed_scaling_factor", 1.0))
    return jnp.zeros((s, e), jnp.float32).at[
        jnp.arange(s)[:, None], topi].set(topv)


def feed_forward(h, lp, experts, arch: Dict[str, Any], quant: Dict[str, Any],
                 alter=None, capacity: Optional[int] = None):
    """The feed-forward block on the normed ``h`` ``[S, D]``: dense where
    ``lp`` holds ``gate_proj``; else the shared expert plus the held
    experts' part of the routed sum (``experts``: this layer's stacks).
    An expert runs on the ``capacity`` rows that chose it, gathered
    (``reference_dots3_note.feed_forward`` has the argument); one chosen
    by more rows takes the plain form over every row."""
    import jax
    import jax.numpy as jnp

    if "router" not in lp:
        return _swiglu(h, _dense(lp["gate_proj"], quant),
                       _dense(lp["up_proj"], quant),
                       _dense(lp["down_proj"], quant))
    scores = jax.nn.sigmoid(h @ lp["router"].astype(jnp.float32))
    first, held = int(arch["first_held"]), int(arch["held"])
    weights = route(scores, lp["router_bias"], arch,
                    alter)[:, first:first + held]
    s = h.shape[0]
    cap = expert_capacity(s, arch) if capacity is None else int(capacity)

    def every_row(acc, w_col, mats):
        return acc + w_col[:, None] * _swiglu(h, *mats)

    def chosen_rows(acc, w_col, mats):
        idx = jnp.nonzero(w_col > 0, size=cap, fill_value=0)[0]
        took = jnp.arange(cap) < jnp.sum(w_col > 0)     # not the filling
        out = _swiglu(h[idx], *mats) * jnp.where(took, w_col[idx],
                                                 0.0)[:, None]
        return acc.at[idx].add(out)

    def one(acc, args):            # the experts one at a time, summed
        w_col, gate, up, down = args
        mats = (_dense(gate, quant), _dense(up, quant), _dense(down, quant))
        if cap >= s:
            return every_row(acc, w_col, mats), None
        return jax.lax.cond(jnp.sum(w_col > 0) <= cap, chosen_rows,
                            every_row, acc, w_col, mats), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        weights.T, experts["experts_gate"], experts["experts_up"],
        experts["experts_down"]))
    shared = _swiglu(h, _dense(lp["shared_gate"], quant),
                     _dense(lp["shared_up"], quant),
                     _dense(lp["shared_down"], quant))
    return shared + routed


def block(x, lp, ex, arch, quant, alter=None):
    eps = float(arch["norm_eps"])
    x = x + attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch,
                      quant, alter)
    return x + feed_forward(
        _rms_norm(x, lp["post_attention_layernorm"], eps), lp, ex, arch,
        quant, alter)


def layer_stack(params: Dict[str, Any], arch: Dict[str, Any]):
    """``(index, that layer's leaves, its routed experts or None)`` of
    the main stack, in the model's order."""
    import jax

    n_dense = int(arch["first_k_dense"])
    for i, lp in enumerate(params["layers"]):
        ex = None
        if i >= n_dense:
            ex = jax.tree.map(lambda a, j=i - n_dense: a[j],
                              params["experts"])
        yield i, lp, ex


def mtp_experts(params: Dict[str, Any]):
    """The MTP block's routed experts: the last of the stacks."""
    import jax

    return jax.tree.map(lambda a: a[-1], params["experts"])


def hidden_states(params, arch, quant, token_ids: Sequence[int], alter=None):
    """The main stack's output ``[S, D]`` float32 BEFORE the final
    norm."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, lp, ex: block(x, lp, ex, arch, quant, alter))
        ids = jnp.asarray(list(token_ids), jnp.int32)
        x = params["embed_tokens"][ids].astype(jnp.float32)
        for _, lp, ex in layer_stack(params, arch):
            x = step(x, lp, ex)
        return x


def _head(params, arch, quant, x, norm):
    import jax

    eps = float(arch["norm_eps"])
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda x, norm, lm_head: _rms_norm(x, norm, eps)
                       @ _dense(lm_head, quant))(x, norm, params["lm_head"])


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, alter=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical tree ``params``. A
    tree marked ``refused`` (``weights_deepseek_v32.canonical_params``:
    the program was outside a limit of ``checks_deepseek_v32``) is
    vouched for by no logits: they come back NaN."""
    x = hidden_states(params, arch, quant, token_ids, alter)
    logits = _head(params, arch, quant, x[first:], params["norm"])
    return logits * float("nan") if params.get("refused") else logits


def mtp_combine(params, arch, quant, hidden, next_emb, alter=None):
    """The MTP module's input rows ``[S, D]``: ``[RMSNorm_e(next_emb) ;
    RMSNorm_h(hidden)] W_eh``, the embedding half first."""
    import jax.numpy as jnp

    alter = alter or {}
    m = params["mtp"]
    eps = float(arch["norm_eps"])
    e = _rms_norm(next_emb, m["enorm"], eps)
    hh = (_rms_norm(hidden, m["hnorm"], eps) if alter.get("hnorm", True)
          else hidden)
    pair = [hh, e] if alter.get("eh_swap") else [e, hh]
    return jnp.concatenate(pair, axis=-1) @ _dense(m["eh_proj"], quant)


def mtp_logits(params, arch, quant, token_ids: Sequence[int], hidden,
               alter=None):
    """The MTP module's logits ``[S, V]`` over the sequence: row i, from
    ``hidden[i]`` (the main stack's pre-norm output, ``hidden_states``)
    and token i + 1, is the distribution of token i + 2. The LAST row
    has no next token (it is given token 0) and means nothing; causal
    attention keeps it from every other row."""
    import jax
    import jax.numpy as jnp

    m = params["mtp"]
    ids = list(token_ids)
    nxt = jnp.asarray(ids[1:] + [0], jnp.int32)

    def run(hidden, emb):
        x = mtp_combine(params, arch, quant, hidden, emb, alter)
        return block(x, m["block"], mtp_experts(params), arch, quant, alter)

    with jax.default_matmul_precision("highest"):
        x = jax.jit(run)(hidden.astype(jnp.float32),
                         params["embed_tokens"][nxt].astype(jnp.float32))
    return _head(params, arch, quant, x, m["shared_head_norm"])


LOGITS_LIMIT = 1.2


def rounding_walk(layers: int) -> float:
    """As ``reference_dots3_note.rounding_walk``: how far bfloat16
    rounding alone carries the program's logits from this reference's
    while no expert and no selected position is swapped."""
    return 2.4 * 2.0 ** -9 * math.sqrt(12.0 * layers)


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end: as the two other routed families',
    1.2, for their reason. This model throws two coins a token (the
    router's eighth against ninth biased score, and now the fourth
    against fifth GROUP; past ``index_topk`` positions the selection's):
    where one falls differently the two sides are different functions of
    the token from there on. It tells logits that are the model's from
    logits that are not (unrelated rows read 1.41); what holds the
    program to a precision is ``layer_limits``, on the reference's own
    inputs, where no coin can fall. Readings: PERF.md section 6, PR 43."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 9.0, "decode_gap_max": 9.0,
                     "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads over four of the window's
    own greedy requests, every streamed token of the SPECULATING engine
    (both tokens of an accepted pair, the resampled token of a rejected
    one) against the reference's best logit at its position. As the two
    other routed families': the MEAN gap tells a sound run from tokens
    of a wrong row, position or slot (a random token lies 3.8 deviations
    down over 16,160 logits; one request of four wrong reads 1.0); the
    widest gap is bounded by the logits' range and decides nothing. A
    verify step that kept a dead row, let row 1 miss row 0 or advanced
    ``pos`` wrongly shows here. Readings: PERF.md section 6, PR 43."""
    del config, kv_cache_dtype
    return dict(SERVED_GAP_LIMITS)


LAYER_LIMITS = {
    "full_attention_prefill": 0.25, "full_attention_decode": 0.25,
    "given_selection_prefill": 0.024, "given_selection_decode": 0.024,
    "ffn_prefill": 0.015, "ffn_decode": 0.015,
    "index_score_rel_l2": 0.02, "index_overlap_min": 0.985,
    "verify_rel_l2": 0.024, "mtp_rel_l2": 0.015, "head_rel_l2": 0.015,
    "accepted_stream": 0.1, "verify_live_mismatch": 0.5,
}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_deepseek_v32.layer_check`` reads: the
    relative L2 of one block's output against this reference's on the
    same bfloat16 input (4,096 rows in 1024-row chunks, the splice,
    verify steps of two rows across 2,048); ``index_overlap_min`` is a
    FLOOR. The names dots3's check has keep dots3's limits (the same
    kernels at the same widths: ``reference_dots3_note.layer_limits``
    has their readings). New here: ``verify_rel_l2`` (BOTH rows of
    two-row verify steps, on the REFERENCE's selection so that no coin
    falls, against the reference's rows n and n + 1: the precision of
    the two-row kernels, limit as ``given_selection_*``);
    ``verify_live_mismatch`` (entries of the decode rows' index scores
    that are live on one side and not on the other: a COUNT, 0 when each
    row is limited at its own position and row 1 sees row 0);
    ``mtp_rel_l2`` (the MTP module's combine ``[enorm(emb) ; hnorm(h)]
    W_eh`` at prefill and decode rows and its head ``shared_head.norm``
    + ``lm_head``; the module's block is one more body of the
    per-layer readings) and ``head_rel_l2`` (final norm + head), limits
    as ``ffn_*`` (one or two int4 linears deep); ``accepted_stream``
    (the largest relative L2 of a cache row, latent or index, that
    forced accepts and rejects left behind against the row the
    reference expects there, plus 1 where ``pos`` is off: sound rows
    read a bfloat16 rounding, a kept dead row reads about 1.4). A
    configuration's own ``layer_limits`` (the tiny preset's) take their
    place. Readings and each control's refusal: PERF.md section 6, PR
    43."""
    return dict(config.get("layer_limits") or LAYER_LIMITS)
