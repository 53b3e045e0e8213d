"""The plain reference of dots3-note-prev: a forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, one layer at a time, NOT
absorbed (K and V are materialised per head from the compressed rows),
no kernel, no cache, no batching, on weights dequantized by plain
arithmetic. No import of the program.

The layer, told by the ``reference`` block of the configuration's file
(``arch``; pre-norm residual, RMSNorm, untied head; ``x`` the normed
input):

- full-attention layer (``arch["layer_types"][i] == "full_attention"``),
  sizes ``arch["full"]``: ``c_q = a_q RMSNorm(x W_qa)``, ``[q_n | q_r]_h =
  c_q W_qb``, ``[c_kv | k_r] = x W_kva``, ``c_kv <- a_kv RMSNorm(c_kv)``,
  rope (channels 2i and 2i+1 together, base ``theta``, no scaling) on
  ``q_r`` and the one shared ``k_r``, ``[k_n | v]_h = c_kv W_kvb``. The
  indexer (``arch["index"]``): ``q_I = c_q W_Iq`` (``heads`` x ``dim``),
  ``k_I = LayerNorm(x W_Ik)`` (weight and bias, eps 1e-6), ``w = x W_Iw *
  heads^-1/2 * dim^-1/2``, rope on the first ``rope`` channels of ``q_I``
  and ``k_I``; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])`` for ``s
  <= t``; ``S_t`` = the ``topk`` positions of largest ``I[t, .]`` (all of
  them while ``t < topk``; ``lax.top_k``: ties to the lower position).
  ``softmax over S_t of (q_n . k_n + q_r . k_r) / sqrt(nope + rope)``, times
  ``v``; head-wise gate ``o_h <- sigmoid(x W_g)_h o_h``; then ``W_o``;
- window layer, sizes ``arch["window"]``: the same without an indexer;
  position ``t`` attends ``s`` in ``[t - window + 1, t]``;
- ``a_q = sqrt(hidden / q_lora_rank)``, ``a_kv = sqrt(hidden /
  kv_lora_rank)`` (``arch["rescale"]``);
- feed-forward: the first ``first_k_dense`` layers dense SwiGLU, the
  others the shared expert plus ``sum_i w_i SwiGLU^(e_i)(x)``: scores
  ``sigmoid(x W_r)`` in float32, choice = top ``experts_per_tok`` of
  ``scores + b``, weights ``scores[chosen] / sum`` times
  ``routed_scaling_factor``.

Departures from the published model: weights are the seeded random
block-quantized planes the program serves, dequantized here as ``(code -
8) * scale``; the configuration's SHARE: of the chosen experts only those
this chip holds add to the sum, in the program and here alike; the
index keys are not Hadamard-rotated (orthogonal on both sides of the dot
product: no score changes). Rows go in blocks of ``ROW_BLOCK`` (their
index scores, selection, attention and feed-forward), heads in groups
(queries, keys and values of one group at a time from the two latents)
and the experts one at a time, so that a 14k-token request fits beside
the weights at the published widths. Two products that are zero by the
model's own definition are not made (PR 38: this pass over four
requests of up to 14.5k tokens was the longest part of a run): an
expert runs on the rows that chose it (``feed_forward``), a window
layer's row block meets the keys of its window, not all of them
(``attention``), and a full layer's rows, their index scores and their
selection stop at the last key a run of row blocks can see
(``_causal_groups``).

``alter`` plants a fault or a lower precision for the controls of
``checks_dots3_note`` (``select: "first"``, ``window: n``, ``gate:
False``, ``router_bias: False``, ``rescale: False``, ``latent_dtype``);
``given`` hands a layer's attention the selection of someone else.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm)

HEAD_GROUP = 8        # heads whose [rows, S] scores are live together
ROW_BLOCK = 512
CAUSAL_GROUPS = 4     # runs of row blocks that stop at their last row's key
FULL, WINDOW = "full_attention", "sliding_attention"
INDEX_NORM_EPS = 1e-6


def _rope(x, positions, theta: float, rd: int):
    """x ``[S, H, d]``: the first ``rd`` channels rotate, 2i with 2i+1."""
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    rot, rest = x[..., :rd], x[..., rd:]
    a, b = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin],
                    axis=-1).reshape(rot.shape)
    return jnp.concatenate([out, rest], axis=-1)


def _layer_norm(x, w, b, eps: float):
    import jax.numpy as jnp

    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _row_blocks(s: int) -> int:
    return ROW_BLOCK if s % ROW_BLOCK == 0 else s


def _causal_groups(blocks: int):
    """``(first, past-last)`` row blocks of up to ``CAUSAL_GROUPS`` runs.
    No row of a run sees a key past the run's last row, so a run's
    scores stop there: a quarter, a half, three quarters and all of the
    keys in place of all of them four times."""
    g = max(1, min(CAUSAL_GROUPS, blocks))
    cuts = [round(i * blocks / g) for i in range(g + 1)]
    return [(a, b) for a, b in zip(cuts, cuts[1:]) if b > a]


def index_scores(y, c_q, lp, arch, quant):
    """``I`` ``[S, S]`` float32, ``-inf`` above the diagonal."""
    import jax
    import jax.numpy as jnp

    ix = arch["index"]
    hi, di, rd = int(ix["heads"]), int(ix["dim"]), int(ix["rope"])
    theta = float(arch["full"]["theta"])
    s = y.shape[0]
    pos = jnp.arange(s)
    q_i = _rope((c_q @ _dense(lp["index_q_proj"], quant)).reshape(s, hi, di),
                pos, theta, rd)
    k_i = _layer_norm(y @ _dense(lp["index_k_proj"], quant),
                      lp["index_k_norm"], lp["index_k_norm_bias"],
                      INDEX_NORM_EPS)
    k_i = _rope(k_i[:, None, :], pos, theta, rd)[:, 0]             # [S, di]
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


def select(scores, topk: int, how: str = "top"):
    """``[S, S]`` bool: the positions each row attends. ``how`` "first"
    is a control: the first ``topk`` positions and not the best."""
    import jax
    import jax.numpy as jnp

    s = scores.shape[0]
    live = scores > -jnp.inf
    if how == "first":
        return live & (jnp.arange(s)[None, :] < topk)
    rb = _row_blocks(s)
    runs = []
    for lo, past in _causal_groups(s // rb):
        ext = past * rb                  # every later score is -inf here
        k = min(topk, ext)

        def rows(blk, k=k):
            _, idx = jax.lax.top_k(blk, k)
            return jnp.zeros(blk.shape, bool).at[
                jnp.arange(blk.shape[0])[:, None], idx].set(True)

        run = jax.lax.map(rows, scores[lo * rb:ext, :ext].reshape(
            past - lo, rb, ext))
        runs.append(jnp.pad(run.reshape(-1, ext), ((0, 0), (0, s - ext))))
    return jnp.concatenate(runs) & live


def attention(y, lp, arch, quant, kind: str, alter=None, given=None,
              probe: Optional[dict] = None):
    """One layer's attention on the normed ``y`` ``[S, D]``, K and V per
    head. ``given``: a ``[S, S]`` selection to use in the place of this
    layer's own; ``probe``: a dict that receives ``index_scores`` and
    ``selected`` of a full layer."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    a = arch["full" if kind == FULL else "window"]
    h, c = int(a["heads"]), int(a["kv_lora_rank"])
    nope, r, vd = int(a["nope"]), int(a["rope"]), int(a["v"])
    eps, hidden = float(arch["norm_eps"]), float(arch["hidden"])
    s = y.shape[0]
    pos = jnp.arange(s)
    rescale = bool(arch.get("rescale", True)) and alter.get("rescale", True)
    c_q = _rms_norm(y @ _dense(lp["q_a_proj"], quant), lp["q_a_layernorm"],
                    eps)
    kv = y @ _dense(lp["kv_a_proj"], quant)
    c_kv = _rms_norm(kv[:, :c], lp["kv_a_layernorm"], eps)
    if rescale:
        c_q = c_q * math.sqrt(hidden / int(a["q_lora_rank"]))
        c_kv = c_kv * math.sqrt(hidden / c)
    theta = float(a["theta"])
    k_pe = _rope(kv[:, None, c:c + r], pos, theta, r)[:, 0]        # [S, r]
    if alter.get("latent_dtype") is not None:
        c_kv = c_kv.astype(alter["latent_dtype"]).astype(jnp.float32)
        k_pe = k_pe.astype(alter["latent_dtype"]).astype(jnp.float32)
    scale = (nope + r) ** -0.5
    if kind == FULL:
        if given is not None:
            allowed = given
        else:
            scores = index_scores(y, c_q, lp, arch, quant)
            allowed = select(scores, int(arch["index"]["topk"]),
                             alter.get("select", "top"))
            if probe is not None:
                probe["index_scores"], probe["selected"] = scores, allowed
    else:
        window = int(alter.get("window", a["window"]))
        d = pos[:, None] - pos[None, :]
        allowed = (d >= 0) & (d < window)

    g = math.gcd(h, HEAD_GROUP)
    rb = _row_blocks(s)
    ok_blocks = allowed.reshape(s // rb, rb, s)
    # A window layer's row block sees its own rows' keys and the window
    # before them: ``span`` keys from the block's start on hold every
    # allowed one, so the others are never multiplied (the mask still
    # decides inside the span). A full layer's rows go in causal runs.
    span = s
    if kind != FULL:
        span = min(s, rb + -(-(window - 1) // rb) * rb)
    groups = _causal_groups(s // rb) if kind == FULL else [(0, s // rb)]

    def heads(args):
        """One group of heads: its queries, keys and values from the two
        latents, its rows in blocks."""
        w_qb, w_kvb = args             # [q_lora, g, nope + r], [c, g, nope + vd]
        q = jnp.einsum("sq,qgd->sgd", c_q, w_qb)
        q_pe = _rope(q[..., nope:], pos, theta, r)
        kvb = jnp.einsum("sc,cgd->sgd", c_kv, w_kvb)
        runs = []
        for lo, past in groups:
            ext = past * rb              # a full layer: causal, no key past
            sp = min(span, ext)
            kn, kp, vv = kvb[:ext, :, :nope], k_pe[:ext], kvb[:ext, :, nope:]

            def rows(rargs, kn=kn, kp=kp, vv=vv, sp=sp):
                qn, qp, ok, t0 = rargs              # [rb, g, .], [rb, ext], []
                kn, kp, vv, ok = (
                    jax.lax.dynamic_slice_in_dim(a, t0, sp, ax)
                    for a, ax in ((kn, 0), (kp, 0), (vv, 0), (ok, 1)))
                sc = (jnp.einsum("sgd,tgd->gst", qn, kn)
                      + jnp.einsum("sgr,tr->gst", qp, kp)) * scale
                probs = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf),
                                       axis=-1)
                return jnp.einsum("gst,tgd->sgd", probs, vv)

            starts = jnp.clip(jnp.arange(lo, past) * rb + rb - sp, 0,
                              ext - sp)
            runs.append(jax.lax.map(rows, (
                q[lo * rb:ext, :, :nope].reshape(past - lo, rb, g, nope),
                q_pe[lo * rb:ext].reshape(past - lo, rb, g, r),
                ok_blocks[lo:past, :, :ext], starts)))
        return jnp.concatenate(runs).reshape(s, g, vd)

    def by_group(w, width):
        w = _dense(w, quant)
        return jnp.moveaxis(w.reshape(w.shape[0], h // g, g, width), 1, 0)

    out = jax.lax.map(heads, (by_group(lp["q_b_proj"], nope + r),
                              by_group(lp["kv_b_proj"], nope + vd)))
    o = jnp.moveaxis(out, 0, 1).reshape(s, h, vd)       # [h/g, S, g, vd] ->
    if alter.get("gate", True):
        o = o * jax.nn.sigmoid(y @ _dense(lp["attn_gate"], quant))[..., None]
    return o.reshape(s, h * vd) @ _dense(lp["o_proj"], quant)


def _rows(fn, x):
    """``fn`` on ``x`` ``[S, D]`` a block of rows at a time."""
    import jax

    rb = _row_blocks(x.shape[0])
    return jax.lax.map(fn, x.reshape(x.shape[0] // rb, rb, -1)).reshape(
        x.shape[0], -1)


def _swiglu(y, gate, up, down):
    import jax

    return _rows(lambda b: (jax.nn.silu(b @ gate) * (b @ up)) @ down, y)


def route(scores, bias, arch: Dict[str, Any]):
    """Sigmoid scores ``[S, E]`` -> weights ``[S, E]`` float32: the
    routing weight of each expert for each token, 0 where not chosen.
    The bias chooses and does not weigh."""
    import jax
    import jax.numpy as jnp

    s, e = scores.shape
    k = int(arch["experts_per_tok"])
    _, topi = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    topv = jnp.take_along_axis(scores, topi, axis=-1)
    if arch.get("norm_topk_prob", True) and k > 1:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    topv = topv * float(arch.get("routed_scaling_factor", 1.0))
    return jnp.zeros((s, e), jnp.float32).at[
        jnp.arange(s)[:, None], topi].set(topv)


def expert_capacity(rows: int, arch: Dict[str, Any]) -> int:
    """Rows an expert's own pass holds: twice what a uniform router
    sends it, in whole 128s; ``rows`` (every row, the plain sum) where
    that is no fewer."""
    even = rows * int(arch["experts_per_tok"]) / int(arch["experts_total"])
    return min(rows, -(-int(2 * even) // 128) * 128)


def feed_forward(h, lp, experts, arch: Dict[str, Any], quant: Dict[str, Any],
                 alter=None, capacity: Optional[int] = None):
    """The feed-forward block on the normed ``h`` ``[S, D]``: dense where
    ``lp`` holds ``gate_proj``; else the shared expert plus the held
    experts' part of the routed sum (``experts``: this layer's stacks).

    An expert's term is ``w[:, e] * SwiGLU_e(h)``, and ``w[:, e]`` is 0
    on every row that did not choose it (31 of 32 rows at the published
    sizes), so each expert runs on the ``capacity`` rows it was chosen
    by, gathered, and its outputs are added back to those rows: the
    same sum, a sixteenth of the products. An expert chosen by more rows
    than ``capacity`` takes the plain form over every row, so no row is
    ever dropped."""
    import jax
    import jax.numpy as jnp

    if "router" not in lp:
        return _swiglu(h, _dense(lp["gate_proj"], quant),
                       _dense(lp["up_proj"], quant),
                       _dense(lp["down_proj"], quant))
    scores = jax.nn.sigmoid(h @ lp["router"].astype(jnp.float32))
    bias = lp["router_bias"]
    if not (alter or {}).get("router_bias", True):
        bias = jnp.zeros_like(bias)
    first, held = int(arch["first_held"]), int(arch["held"])
    weights = route(scores, bias, arch)[:, first:first + held]
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


def layer_stack(params: Dict[str, Any], arch: Dict[str, Any]):
    """``(index, kind, that layer's leaves, its routed experts or None)``
    in the model's order."""
    import jax

    n_dense = int(arch["first_k_dense"])
    for i, lp in enumerate(params["layers"]):
        ex = None
        if i >= n_dense:
            ex = jax.tree.map(lambda a, j=i - n_dense: a[j],
                              params["experts"])
        yield i, arch["layer_types"][i], lp, ex


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, alter=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical tree ``params``
    (``layers`` one dict a layer with ``kv_b_proj`` one linear,
    ``experts`` stacked over the expert layers).

    A tree marked ``refused`` (``weights_dots3_note.canonical_params``:
    the program was outside a limit of ``checks_dots3_note``, layer by
    layer on the reference's own inputs) is vouched for by no logits:
    they come back NaN, so that every comparison the harness makes with
    them reads not correct."""
    import jax
    import jax.numpy as jnp

    eps = float(arch["norm_eps"])

    def layer(x, lp, ex, kind):
        x = x + attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch,
                          quant, kind, alter)
        return x + feed_forward(
            _rms_norm(x, lp["post_attention_layernorm"], eps), lp, ex, arch,
            quant, alter)

    with jax.default_matmul_precision("highest"):
        step = jax.jit(layer, static_argnums=3)
        ids = jnp.asarray(list(token_ids), jnp.int32)
        x = params["embed_tokens"][ids].astype(jnp.float32)
        for _, kind, lp, ex in layer_stack(params, arch):
            x = step(x, lp, ex, kind)
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(x, norm, eps)
                       @ _dense(lm_head, quant))
        logits = head(x[first:], params["norm"], params["lm_head"])
        return logits * jnp.nan if params.get("refused") else logits


LOGITS_LIMIT = 1.2


def rounding_walk(layers: int) -> float:
    """How far bfloat16 rounding alone carries the program's logits from
    this reference's while no expert and no selected position is
    swapped: about twelve tensors a layer rounded at 2**-9, walking
    randomly through the depth, times the 2.4 the dense configurations
    measured between that estimate and the chip. The CPU tests hold the
    program to it at toy widths, where the router's scores lie far
    apart."""
    return 2.4 * 2.0 ** -9 * math.sqrt(12.0 * layers)


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end. As ``reference_deepseek_v2.
    tolerance``: it tells logits that are the model's from logits that
    are not (unrelated rows read 1.41), and nothing finer, because this
    model throws TWO coins a token: the router's (top 8 of 256 sigmoid
    scores plus a bias: the eighth and ninth lie closer than the
    bfloat16 walk of the hidden state for some token in most sequences)
    and, past ``index_topk`` positions, the selection's (the 2048th and
    2049th index score). Where one falls differently the two sides are
    different functions of the token from there on. What holds the
    program to a precision is ``layer_limits``, on the reference's own
    inputs, where neither coin can fall. Readings (my chip runs, PR 33,
    published widths, eight seeds; 32 + 8 positions, so no selection
    binds in this comparison): prefill's position 0.034-0.058, the 8
    decoded positions 0.039-0.053; the hidden state's walk and a swapped
    eighth expert here and there. 1.2 lies twenty times over them and
    under 1.41; a seed on which a whole set of experts swaps reads what
    DeepSeek-V2's did (0.39-0.58), and a limit between would not hold
    over the seeds a check draws."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 9.0, "decode_gap_max": 9.0,
                     "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads, over four of the
    window's own greedy requests of 4k-14k prompt tokens: the MEAN gap
    tells a sound run (the program's token is the reference's best or
    close under it) from tokens of a wrong row, position, ring column or
    slot (a random token lies 3.9 deviations down over 19,008 logits;
    one request of four wrong reads 1.0). The widest gap is bounded by
    the logits' range and decides nothing, as in
    ``reference_deepseek_v2.served_gap_limits``; its limit lies past that
    range and says so. Readings (my chip runs, PR 33, eight seeds, four
    requests of up to 14,500 tokens, 548-1,124 served tokens a run):
    ``decode_gap_mean`` 0.083-0.122 (the reference's own best token at
    56-62 % of positions), ``prefill_gap_max`` 0.0-1.06,
    ``decode_gap_max`` 0.96-2.96."""
    del config, kv_cache_dtype
    return dict(SERVED_GAP_LIMITS)


LAYER_LIMITS = {
    "full_attention_prefill": 0.25, "full_attention_decode": 0.25,
    "window_attention_prefill": 0.02, "window_attention_decode": 0.02,
    "given_selection_prefill": 0.024, "given_selection_decode": 0.024,
    "ffn_prefill": 0.015, "ffn_decode": 0.015,
    "index_score_rel_l2": 0.02, "index_overlap_min": 0.985,
}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_dots3_note.layer_check`` reads: the
    relative L2 of one block's output against this reference's on the
    same bfloat16 input, the largest over the layers (4,096 rows in
    1024-row chunks, the splice, 8 decoded rows); ``index_overlap_min``
    is a FLOOR. A configuration's own ``layer_limits`` (the tiny
    preset's) take their place.

    Readings (my chip runs, PR 33, published widths, 14 layers, six
    seeds sound; controls one seed each, the reference with the fault in
    the program's place):

    - ``given_selection_*`` (a full layer's attention on the REFERENCE's
      selection: its precision): sound 0.0105-0.0106 / 0.0109-0.0116;
      latent rows in float8_e5m2, the precision below: 0.0486 / 0.0508.
      Limit 0.024, the geometric mean: 2.1 times over the largest sound
      reading, 2.0 times under the control. No gate 1.01, no rescale 0.96.
    - ``window_attention_*``: sound 0.0090-0.0091 / 0.0095-0.0100; a
      window of 512 (one position short) 0.0386 / 0.0376, float8_e5m2
      0.0410 / 0.0433. Limit 0.02: twice the largest sound reading, 1.9
      times under the smallest control.
    - ``index_score_rel_l2``: sound 0.0052; no rescale 0.553. Limit 0.02.
    - ``index_overlap_min``: sound 0.9946-0.9956 (9-11 of a row's 2048
      positions lie on the other side of the 2048th score: the
      selection's coin, bfloat16 against float32 scores); the first 2048
      positions instead of the top 0.458. Floor 0.985, 31 positions:
      three times the sound distance.
    - ``full_attention_*`` (the program's OWN selection): sound 0.0256-
      0.0274 / 0.0515-0.1249 (8 decoded rows: with random weights the
      softmax over 2048 positions is nearly flat, so n swapped positions
      move a row by about sqrt(2 n / 2048): 0.10 at n = 11, 0.17 at the
      overlap's floor); the first 2048 positions 0.381 / 0.881, no gate
      1.01 / 1.07, no rescale 0.96 / 0.97. Limit 0.25: twice the largest
      sound reading, 1.5 times under the selection control's smallest
      (which the overlap's floor refuses by a factor of 35 besides). It
      tells a selection that is not the top from the coin; the
      precision is ``given_selection``'s to hold.
    - ``ffn_*``: sound 0.0044 / 0.0045-0.0046; the bias left out of the
      choice 0.0819 / 0.1035. Limit 0.015, as DeepSeek-V2's routed layer.
    """
    return dict(config.get("layer_limits") or LAYER_LIMITS)
