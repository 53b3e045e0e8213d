"""The plain reference of AFMoE (Trinity-Mini): a forward pass in
straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, one layer at a time, no kernel,
no cache, no batching, on weights dequantized by plain arithmetic. No
import of the program.

The model, told by the ``reference`` block of the configuration's file
(``arch``). ``x = embed[token] * embed_scale`` (``sqrt(hidden)`` under
``mup_enabled``). Every norm is an RMSNorm with a learned weight, eps
``norm_eps``. Layer ``i`` is a window layer where ``arch["pattern"][i]``
is 1, a full layer where 0:

- attention: ``h = input_layernorm(x)``; ``q = h W_q`` ``[S, H, d]``,
  ``k = h W_k`` ``[S, G, d]``, ``v = h W_v`` ``[S, G, d]``, ``g = h W_g``
  ``[S, H d]``; q and k through an RMSNorm over the ``d`` values of each
  head (``q_norm`` / ``k_norm`` ``[d]``); in a WINDOW layer only, rotary
  on all ``d`` dims of q and k after that norm (half-rotation form, base
  ``theta``), a FULL layer none; ``s_ij = q_i . k_j / sqrt(d)``, head n
  reading KV head ``n // (H / G)``; ``j <= i``, and in a window layer
  ``i - j < window`` (the query's own position counted); plain softmax;
  ``a = (concat(o) * sigmoid(g)) W_o``; ``x = x +
  post_attention_layernorm(a)``: the second norm is on the BRANCH;
- feed-forward: ``h = pre_mlp_layernorm(x)``; ``arch["moe"][i]`` 0:
  dense SwiGLU; 1: ``p = sigmoid(h W_r)`` in float32, choice = top
  ``experts_per_tok`` of ``p + expert_bias``, weights ``p[choice] / sum``
  times ``routed_scaling_factor`` (the published ``route_scale``),
  ``sum_e w_e SwiGLU_e(h)`` PLUS one shared SwiGLU expert on every row,
  unweighted; ``x = x + post_mlp_layernorm(y)``.
- final RMSNorm, an untied head.

Departures from the published model, each also under ``assumed`` in the
configuration's file:

- weights are the seeded random block-quantized planes the program
  serves, dequantized here as ``(code - 8) * scale``; the router's
  ``expert_bias`` and every norm's weight are SEEDED
  (``weights_afmoe``), since zeros and ones would make them
  unobservable;
- the configuration's SHARE: of the chosen experts only those this chip
  holds add to the sum (the shared expert is whole on every chip), and
  the vocabulary is the chip's slice, in the program and here alike.

One kind of product that is zero by the model's own definition is not
made, so that four requests of 8k tokens through 32 layers meet the
harness's budget after the window: a window layer's row block meets the
keys of its band, not all of them (``attention``), and a full layer's
rows go in causal runs that stop at the last key a run can see. The
routed sum is the plain one, every held expert on every row: the other
routed references' short cut (an expert on the rows that chose it, by
``nonzero``, a gather and a scatter-add) did not come back from the
chip at 3,080 rows of these widths (my chip run, PR 49: PERF.md 7).

``alter`` plants a fault or a lower precision for the controls of
``checks_afmoe`` (``gate: False``, ``qk_norm: False``, ``rotary_full:
True``, ``window: n``, ``route_scale: False``, ``shared: False``,
``ring_dtype``: the WINDOW layers' K and V rounded to a lower
precision).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm, _rope)
from harness.reference_dots3_note import (_causal_groups, _row_blocks,
                                          _swiglu, route,
                                          rounding_walk)  # noqa: F401


def attention(y, lp, arch: Dict[str, Any], quant: Dict[str, Any],
              window_layer: bool, alter=None):
    """One layer's attention on the normed ``y`` ``[S, D]``, before the
    branch's norm."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    h, g, d = int(arch["heads"]), int(arch["kv_heads"]), int(arch["head_dim"])
    eps = float(arch["norm_eps"])
    s = y.shape[0]
    pos = jnp.arange(s)
    q = (y @ _dense(lp["q_proj"], quant)).reshape(s, h, d)
    k = (y @ _dense(lp["k_proj"], quant)).reshape(s, g, d)
    v = (y @ _dense(lp["v_proj"], quant)).reshape(s, g, d)
    if alter.get("qk_norm", True):
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    if window_layer or alter.get("rotary_full"):
        q = _rope(q, pos, float(arch["theta"]), d, False)
        k = _rope(k, pos, float(arch["theta"]), d, False)
    if window_layer and alter.get("ring_dtype") is not None:
        k = k.astype(alter["ring_dtype"]).astype(jnp.float32)
        v = v.astype(alter["ring_dtype"]).astype(jnp.float32)
    window = int(alter.get("window", arch["window"])) if window_layer else 0
    scale = d ** -0.5
    rb = _row_blocks(s)
    # a window layer's row block sees its own rows' keys and the window
    # before them: ``span`` keys hold every allowed one
    span = min(s, rb + -(-(window - 1) // rb) * rb) if window else s
    groups = [(0, s // rb)] if window else _causal_groups(s // rb)

    def one_kv_head(args):
        qg, kg, vg = args             # [S, H/G, d], [S, d], [S, d]
        runs = []
        for lo, past in groups:
            ext = past * rb       # a full layer: no key past the run's rows
            sp = min(span, ext)

            def rows(rargs, sp=sp, ext=ext):
                qb, t0, r0 = rargs                         # [rb, H/G, d]
                kk = jax.lax.dynamic_slice_in_dim(kg[:ext], t0, sp, 0)
                vv = jax.lax.dynamic_slice_in_dim(vg[:ext], t0, sp, 0)
                dist = (r0 + jnp.arange(rb))[:, None] \
                    - (t0 + jnp.arange(sp))[None, :]
                ok = dist >= 0
                if window:
                    ok &= dist < window
                sc = jnp.where(ok[None], jnp.einsum("sgd,td->gst", qb, kk)
                               * scale, -jnp.inf)
                return jnp.einsum("gst,td->sgd", jax.nn.softmax(sc, axis=-1),
                                  vv)

            starts = jnp.arange(lo, past) * rb
            runs.append(jax.lax.map(rows, (
                qg[lo * rb:ext].reshape(past - lo, rb, h // g, d),
                jnp.clip(starts + rb - sp, 0, ext - sp), starts)))
        return jnp.concatenate(runs).reshape(s, h // g, d)

    out = jax.lax.map(one_kv_head, (
        jnp.moveaxis(q.reshape(s, g, h // g, d), 1, 0),
        jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0)))
    o = jnp.moveaxis(out, 0, 1).reshape(s, h * d)       # [G, S, H/G, d] ->
    if alter.get("gate", True):
        o = o * jax.nn.sigmoid(y @ _dense(lp["g_proj"], quant))
    return o @ _dense(lp["o_proj"], quant)


def feed_forward(h, lp, experts, arch: Dict[str, Any], quant: Dict[str, Any],
                 alter=None, share=None):
    """The feed-forward block on the normed ``h`` ``[S, D]``, before the
    branch's norm: dense where ``lp`` holds ``gate_proj``; else the
    shared expert plus the held experts' part of the routed sum
    (``experts``: this layer's stacks; ``share``: ``(first_held, held)``
    in the place of the configuration's), in the plain form: every held
    expert on every row, times the row's weight for it (0 where the row
    did not choose it)."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    if "router" not in lp:
        return _swiglu(h, _dense(lp["gate_proj"], quant),
                       _dense(lp["up_proj"], quant),
                       _dense(lp["down_proj"], quant))
    scores = jax.nn.sigmoid(h @ lp["router"].astype(jnp.float32))
    if not alter.get("route_scale", True):
        arch = {**arch, "routed_scaling_factor": 1.0}
    first, held = share or (int(arch["first_held"]), int(arch["held"]))
    weights = route(scores, lp["router_bias"], arch)[:, first:first + held]

    def one(acc, args):            # the experts one at a time, summed
        w_col, gate, up, down = args
        return acc + w_col[:, None] * _swiglu(
            h, _dense(gate, quant), _dense(up, quant),
            _dense(down, quant)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        weights.T, experts["experts_gate"], experts["experts_up"],
        experts["experts_down"]))
    if not alter.get("shared", True):
        return routed
    return routed + _swiglu(h, _dense(lp["shared_gate"], quant),
                            _dense(lp["shared_up"], quant),
                            _dense(lp["shared_down"], quant))


def layer_stack(params: Dict[str, Any], arch: Dict[str, Any]):
    """``(index, window layer?, that layer's leaves, its routed experts
    or None)`` in the model's order, out of the canonical tree's
    stacks."""
    import jax

    at = 0
    for i in range(int(arch["layers"])):
        lp = jax.tree.map(lambda a, i=i: a[i], params["attn"])
        ex = None
        if arch["moe"][i]:
            lp.update(jax.tree.map(lambda a, j=at: a[j], params["moe"]))
            ex = jax.tree.map(lambda a, j=at: a[j], params["experts"])
            at += 1
        else:
            lp.update(jax.tree.map(lambda a, j=i - at: a[j],
                                   params["dense"]))
        yield i, bool(arch["pattern"][i]), lp, ex


def layer(x, lp, ex, arch, quant, window_layer: bool, alter=None):
    """One decoder layer on the stream ``x`` ``[S, D]``."""
    eps = float(arch["norm_eps"])
    a = attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch, quant,
                  window_layer, alter)
    x = x + _rms_norm(a, lp["post_attention_layernorm"], eps)
    f = feed_forward(_rms_norm(x, lp["pre_mlp_layernorm"], eps), lp, ex,
                     arch, quant, alter)
    return x + _rms_norm(f, lp["post_mlp_layernorm"], eps)


def embed(params: Dict[str, Any], arch: Dict[str, Any], token_ids):
    import jax.numpy as jnp

    ids = jnp.asarray(list(token_ids), jnp.int32)
    return (params["embed_tokens"][ids].astype(jnp.float32)
            * float(arch["embed_scale"]))


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, alter=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical tree ``params``.

    A tree marked ``refused`` (``weights_afmoe.canonical_params``: the
    program was outside a limit of ``checks_afmoe``, layer by layer on
    the reference's own inputs) is vouched for by no logits: they come
    back NaN, so that every comparison the harness makes with them reads
    not correct."""
    import jax
    import jax.numpy as jnp

    eps = float(arch["norm_eps"])
    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, lp, ex, w: layer(x, lp, ex, arch, quant, w,
                                                  alter), static_argnums=3)
        x = embed(params, arch, token_ids)
        for _, window_layer, lp, ex in layer_stack(params, arch):
            x = step(x, lp, ex, window_layer)
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(x, norm, eps)
                       @ _dense(lm_head, quant))
        logits = head(x[first:], params["norm"], params["lm_head"])
        return logits * jnp.nan if params.get("refused") else logits


LOGITS_LIMIT = 1.2


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end. As ``reference_mimo_v2.tolerance``
    and for its reason: the model throws a coin a token and expert layer
    (top 8 of 128 sigmoid scores plus a bias, thirty times a token), and
    where it falls differently the two sides are different functions of
    the token from there on. The bound tells logits that are the model's
    from logits that are not (unrelated rows read 1.41) and nothing
    finer; what holds the program to a precision is ``layer_limits``, on
    the reference's own inputs, where the coin cannot fall. The
    readings over six seeds are in PERF.md 2 (PR 49). The CPU tests hold
    the program to ``rounding_walk`` at toy widths."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 9.0, "decode_gap_max": 9.0,
                     "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads, over four of the
    window's own greedy requests, the routed families' (``reference_
    mimo_v2.served_gap_limits`` has the derivation): the MEAN gap tells
    a sound run (the program's token is the reference's best or close
    under it) from tokens of a wrong row, position, ring column or slot
    (a random token lies 4.1 deviations down over 50,048 logits; one
    request of four wrong reads 1.0). The widest gap is bounded by the
    logits' range and decides nothing; its limit lies past that range
    and says so. Readings: PERF.md 2 (PR 49)."""
    del config, kv_cache_dtype
    return dict(SERVED_GAP_LIMITS)


LAYER_LIMITS = {
    "full_attention_prefill": 0.02, "full_attention_decode": 0.02,
    "window_attention_prefill": 0.02, "window_attention_decode": 0.02,
    "ffn_prefill": 0.015, "ffn_decode": 0.015,
}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_afmoe.layer_check`` reads: the relative L2
    of one block's output (BEFORE the branch's norm, which would divide
    a wrong scale away) against this reference's on the same bfloat16
    input, the largest over the layers checked (3,072 rows in 1024-row
    chunks, the splice into a wrapped ring, 8 decoded rows). A
    configuration's own ``layer_limits`` (the tiny preset's) take their
    place. The limits are the window-and-full family's (``reference_
    mimo_v2.layer_limits``): attention 0.02, feed-forward 0.015; the
    sound readings and each control's, which the limits must part, are
    in PERF.md 2 (my chip runs, PR 49)."""
    return dict(config.get("layer_limits") or LAYER_LIMITS)
