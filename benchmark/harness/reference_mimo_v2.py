"""The plain reference of MiMo-V2.5's language model: a forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, one layer at a time, no kernel,
no cache, no batching, on weights dequantized by plain arithmetic. No
import of the program.

The layer, told by the ``reference`` block of the configuration's file
(``arch``; pre-norm residual, RMSNorm eps ``norm_eps``, untied head;
``y`` the normed input), kind ``arch["pattern"][i]`` (0 = full, 1 =
window) with the sizes ``arch["full"]`` / ``arch["window"]``:

- attention: ``q = y W_q`` ``[S, H, d_qk]``, ``k = y W_k`` ``[S, G,
  d_qk]``, ``v = value_scale * (y W_v)`` ``[S, G, d_v]``; rotary on the
  first ``rotary_dim`` dims of q and k, half-rotation form (dim i with
  dim i + rotary_dim / 2), base ``theta`` of the kind, the other dims
  pass; ``s_ij = q_i . k_j / sqrt(d_qk)``, head n reading KV head ``n //
  (H / G)``; ``j <= i``, and in a window layer ``i - j < window`` (the
  query's own position counted). A kind with ``sink`` appends one learned
  scalar ``b_n`` a query head to each row's logits as a column with no
  value: ``p_ij = exp(s_ij - m_i) / (sum_j' exp(s_ij' - m_i) + exp(b_n -
  m_i))``, ``m_i`` the maximum over the row's keys and ``b_n``. ``o_i =
  sum_j p_ij v_j``; then ``W_o``;
- feed-forward, ``arch["moe"][i]`` 0: dense SwiGLU; 1: ``g = sigmoid(y
  W_r)`` in float32, choice = top ``experts_per_tok`` of ``g + e_bias``,
  weights ``g[choice] / sum`` times ``routed_scaling_factor``, ``sum_e
  w_e SwiGLU_e(y)``. No shared expert.

Departures from the published model, each also under ``assumed`` in the
configuration's file:

- weights are the seeded random block-quantized planes the program
  serves, dequantized here as ``(code - 8) * scale``; the sink ``b_n``
  and the router's ``e_bias`` are SEEDED (``weights_mimo_v2``), since
  zeros would make both mechanisms unobservable;
- the configuration's SHARE: of the chosen experts only those this chip
  holds add to the sum, and the vocabulary is the chip's slice, in the
  program and here alike;
- the rotary form (half-rotation) and the rotary dims (``int(192 *
  0.334) = 64``) are this reading of ``partial_rotary_factor``;
- ``attention_chunk_size`` and ``hybrid_block_size`` are read by
  nothing; the vision and audio towers and the MTP layers are not built.

Two products that are zero by the model's own definition are not made,
so that four requests of 14k tokens meet the harness's budget after the
window: an expert runs on the rows that chose it (``feed_forward``), and
a window layer's row block meets the keys of its band, not all of them
(``attention``); a full layer's rows go in causal runs that stop at the
last key a run can see.

``alter`` plants a fault or a lower precision for the controls of
``checks_mimo_v2`` (``sink: False``, ``value_scale: False``,
``rotary_all: True``, ``window: n``, ``router_bias: False``,
``kv_dtype``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm, _rope)
from harness.reference_dots3_note import (_causal_groups, _row_blocks,
                                          _swiglu, expert_capacity, route,
                                          rounding_walk)  # noqa: F401


def attention(y, lp, arch: Dict[str, Any], quant: Dict[str, Any],
              window_layer: bool, alter=None):
    """One layer's attention on the normed ``y`` ``[S, D]``."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    a = arch["window" if window_layer else "full"]
    h, g = int(a["heads"]), int(a["kv_heads"])
    dk, dv = int(a["head_dim"]), int(a["v_head_dim"])
    s = y.shape[0]
    pos = jnp.arange(s)
    rd = dk if alter.get("rotary_all") else int(arch["rotary_dim"])
    theta = float(a["theta"])
    q = _rope((y @ _dense(lp["q_proj"], quant)).reshape(s, h, dk), pos,
              theta, rd, False)
    k = _rope((y @ _dense(lp["k_proj"], quant)).reshape(s, g, dk), pos,
              theta, rd, False)
    v = (y @ _dense(lp["v_proj"], quant)).reshape(s, g, dv)
    if alter.get("value_scale", True):
        v = v * float(arch["value_scale"])
    if alter.get("kv_dtype") is not None:
        k = k.astype(alter["kv_dtype"]).astype(jnp.float32)
        v = v.astype(alter["kv_dtype"]).astype(jnp.float32)
    sink = None
    if a.get("sink") and alter.get("sink", True):
        sink = lp["sink"].astype(jnp.float32).reshape(g, h // g)
    window = int(alter.get("window", a.get("window", 0))) if window_layer \
        else 0
    scale = dk ** -0.5
    rb = _row_blocks(s)
    # a window layer's row block sees its own rows' keys and the window
    # before them: ``span`` keys hold every allowed one
    span = min(s, rb + -(-(window - 1) // rb) * rb) if window else s
    groups = [(0, s // rb)] if window else _causal_groups(s // rb)

    def one_kv_head(args):
        qg, kg, vg, bg = args     # [S, H/G, dk], [S, dk], [S, dv], [H/G]
        runs = []
        for lo, past in groups:
            ext = past * rb       # a full layer: no key past the run's rows
            sp = min(span, ext)

            def rows(rargs, sp=sp, ext=ext):
                qb, t0, r0 = rargs                         # [rb, H/G, dk]
                kk = jax.lax.dynamic_slice_in_dim(kg[:ext], t0, sp, 0)
                vv = jax.lax.dynamic_slice_in_dim(vg[:ext], t0, sp, 0)
                d = (r0 + jnp.arange(rb))[:, None] \
                    - (t0 + jnp.arange(sp))[None, :]
                ok = d >= 0
                if window:
                    ok &= d < window
                sc = jnp.where(ok[None], jnp.einsum("sgd,td->gst", qb, kk)
                               * scale, -jnp.inf)
                m = jnp.max(sc, axis=-1, keepdims=True)
                if sink is not None:
                    m = jnp.maximum(m, bg[:, None, None])
                p = jnp.exp(sc - m)
                den = jnp.sum(p, axis=-1, keepdims=True)
                if sink is not None:
                    den = den + jnp.exp(bg[:, None, None] - m)
                return jnp.einsum("gst,td->sgd", p / den, vv)

            starts = jnp.arange(lo, past) * rb
            runs.append(jax.lax.map(rows, (
                qg[lo * rb:ext].reshape(past - lo, rb, h // g, dk),
                jnp.clip(starts + rb - sp, 0, ext - sp), starts)))
        return jnp.concatenate(runs).reshape(s, h // g, dv)

    out = jax.lax.map(one_kv_head, (
        jnp.moveaxis(q.reshape(s, g, h // g, dk), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
        sink if sink is not None else jnp.zeros((g, h // g), jnp.float32)))
    o = jnp.moveaxis(out, 0, 1).reshape(s, h * dv)      # [G, S, H/G, dv] ->
    return o @ _dense(lp["o_proj"], quant)


def feed_forward(h, lp, experts, arch: Dict[str, Any], quant: Dict[str, Any],
                 alter=None, capacity=None, share=None):
    """The feed-forward block on the normed ``h`` ``[S, D]``: dense where
    ``lp`` holds ``gate_proj``; else the held experts' part of the routed
    sum (``experts``: this layer's stacks; ``share``: ``(first_held,
    held)`` in the place of the configuration's). Each expert runs on
    the ``capacity`` rows it was chosen by, gathered (its weight is 0 on
    every other row); an expert chosen by more rows takes the plain form
    over every row, so no row is ever dropped. Nothing is shared."""
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
    first, held = share or (int(arch["first_held"]), int(arch["held"]))
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
    return routed


def layer_stack(params: Dict[str, Any], arch: Dict[str, Any]):
    """``(index, window layer?, that layer's leaves, its routed experts
    or None)`` in the model's order."""
    import jax

    at = 0
    for i, lp in enumerate(params["layers"]):
        ex = None
        if arch["moe"][i]:
            ex = jax.tree.map(lambda a, j=at: a[j], params["experts"])
            at += 1
        yield i, bool(arch["pattern"][i]), lp, ex


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, alter=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical tree ``params``
    (``layers`` one dict a layer with q / k / v apart, ``experts``
    stacked over the expert layers).

    A tree marked ``refused`` (``weights_mimo_v2.canonical_params``: the
    program was outside a limit of ``checks_mimo_v2``, layer by layer on
    the reference's own inputs) is vouched for by no logits: they come
    back NaN, so that every comparison the harness makes with them reads
    not correct."""
    import jax
    import jax.numpy as jnp

    eps = float(arch["norm_eps"])

    def layer(x, lp, ex, window_layer):
        x = x + attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch,
                          quant, window_layer, alter)
        return x + feed_forward(
            _rms_norm(x, lp["post_attention_layernorm"], eps), lp, ex, arch,
            quant, alter)

    with jax.default_matmul_precision("highest"):
        step = jax.jit(layer, static_argnums=3)
        ids = jnp.asarray(list(token_ids), jnp.int32)
        x = params["embed_tokens"][ids].astype(jnp.float32)
        for _, window_layer, lp, ex in layer_stack(params, arch):
            x = step(x, lp, ex, window_layer)
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(x, norm, eps)
                       @ _dense(lm_head, quant))
        logits = head(x[first:], params["norm"], params["lm_head"])
        return logits * jnp.nan if params.get("refused") else logits


LOGITS_LIMIT = 1.2


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end. As ``reference_dots3_note.tolerance``
    and for its first reason: the model throws a coin a token and expert
    layer (top 8 of 256 sigmoid scores plus a bias; the eighth and ninth
    lie closer than the bfloat16 walk of the hidden state for some token
    in most sequences), and where it falls differently the two sides are
    different functions of the token from there on. The bound tells
    logits that are the model's from logits that are not (unrelated rows
    read 1.41) and nothing finer; what holds the program to a precision
    is ``layer_limits``, on the reference's own inputs, where the coin
    cannot fall. Readings (my chip runs, PR 45, published widths, ten
    seeds; 32 + 8 positions): prefill's position 0.016-0.056, the 8
    decoded positions 0.017-0.037. 1.2 lies twenty times over them and
    under 1.41; a seed on which a whole set of experts swaps reads what
    DeepSeek-V2's did (0.39-0.58), and a limit between would not hold
    over the seeds a check draws. The CPU tests hold the program to
    ``rounding_walk`` at toy widths."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 9.0, "decode_gap_max": 9.0,
                     "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads, over four of the
    window's own greedy requests: the MEAN gap tells a sound run (the
    program's token is the reference's best or close under it) from
    tokens of a wrong row, position, ring column or slot (a random token
    lies 3.9 deviations down over 19,072 logits; one request of four
    wrong reads 1.0). The widest gap is bounded by the logits' range and
    decides nothing, as in ``reference_dots3_note.served_gap_limits``;
    its limit lies past that range and says so. Readings (my chip runs,
    PR 45, ten seeds, four requests of up to 13,085 tokens, 2,331-2,900
    served tokens a run): ``decode_gap_mean`` 0.0016-0.0029 (the
    reference's own best token at 92-95 % of positions),
    ``prefill_gap_max`` 0.0-0.15, ``decode_gap_max`` 0.15-0.26."""
    del config, kv_cache_dtype
    return dict(SERVED_GAP_LIMITS)


LAYER_LIMITS = {
    "full_attention_prefill": 0.02, "full_attention_decode": 0.02,
    "window_attention_prefill": 0.02, "window_attention_decode": 0.02,
    "ffn_prefill": 0.015, "ffn_decode": 0.015,
}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_mimo_v2.layer_check`` reads: the relative
    L2 of one block's output against this reference's on the same
    bfloat16 input, the largest over the layers checked (2,048 rows in
    1024-row chunks, the splice into a wrapped ring, 8 decoded rows). A
    configuration's own ``layer_limits`` (the tiny preset's) take their
    place.

    Readings (my chip runs, PR 45, published widths, layers 0, 1 and 5,
    eleven seeds sound; controls seed ...009, the reference with the
    fault in the program's place):

    - ``full_attention_*``: sound 0.0059 / 0.0062-0.0065; K and V rows in
      float8_e5m2, the precision below: 0.0731 / 0.0790. ``window_
      attention_*``: sound 0.0056-0.0057 / 0.0056-0.0058; float8_e5m2
      0.0666 / 0.0692; a window of 127 (one position short) 0.0752 /
      0.0841; the sink dropped 0.490 / 0.412. Both kinds: the value scale
      dropped 0.414, rotary on all 192 dims 0.94-1.10. Limit 0.02, near
      the geometric mean of the largest sound reading and the smallest
      control (0.0207): 3.1 times over the one, 3.3 times under the
      other.
    - ``ffn_*``: sound 0.0045 / 0.0044-0.0045; the bias left out of the
      choice 0.689 / 0.746. Limit 0.015, as the other routed families'.
    """
    return dict(config.get("layer_limits") or LAYER_LIMITS)
