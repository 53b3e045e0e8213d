"""The plain reference of SDAR-MoE (SDAR-30B-A3B-Chat): a forward pass
in straightforward ``jax.numpy`` and float32 under
``default_matmul_precision("highest")``, one layer at a time, no kernel,
no cache, no batching, on weights dequantized by plain arithmetic. No
import of the program.

The model, told by the ``reference`` block of the configuration's file
(``arch``). ``x = embed[token]``, unscaled. Every norm is an RMSNorm
with a learned weight, eps ``norm_eps``. All ``layers`` layers alike:

- attention: ``h = input_layernorm(x)``; ``q = h W_q`` ``[S, H, d]``,
  ``k = h W_k`` ``[S, G, d]``, ``v = h W_v`` ``[S, G, d]``; q and k
  through an RMSNorm over the ``d`` values of each head (``q_norm`` /
  ``k_norm`` ``[d]``), then rotary on all ``d`` dims (half-rotation
  form, base ``theta``); ``s_ij = q_i . k_j / sqrt(d)``, head n reading
  KV head ``n // (H / G)``; **key j is live for query i iff ``j // B <=
  i // B``** (``B = arch["block"]``, positions from the sequence's first
  token); plain softmax; ``x = x + concat(o) W_o``;
- experts: ``h = post_attention_layernorm(x)``; ``p = softmax(h W_r)``
  over ALL ``experts_total`` logits in float32, the top
  ``experts_per_tok``, their scores renormalised to sum 1; ``x = x +
  sum_e w_e SwiGLU_e(h)`` over the chosen experts HELD here; nothing
  shared, no bias, no scaling factor;
- final RMSNorm, an untied head; the logit row at position ``i``
  predicts the token AT position ``i``.

``all_logits`` is ONE pass over the ids under that mask: a position
holds the MASK id where the ids say so, and nothing else tells a noised
row from a final one. ``replay`` is the same layers on SEVERAL streams
at once, for what a block family's engine served (``generation_sdar_
moe.served_gaps``): stream 0 is the final sequence, every other stream a
noised copy of the generated stretch (``[G]`` ids from position
``start`` on, a block's rows as ONE denoise pass saw them), whose rows
read the FINAL stream's keys below their block and their OWN stream's
keys inside it: what the engine's pass read, the stored K/V of earlier
blocks and the block's own rows. One stream alone is ``all_logits``: the
two share every line. Beside each served token's gap it returns every
row's CONFIDENCE (the log of ``x0_p``), and ``transfer`` / ``owed`` are
the generation's rule for which MASK rows a pass commits, in plain
arithmetic: with them the replay holds the program to the rows it chose
as well as to the tokens.

Departures from the published model, each also under ``assumed`` in the
configuration's file: weights are the seeded random block-quantized
planes the program serves, dequantized here as ``(code - 8) * scale``,
norms seeded around 1; the configuration's SHARE (of the chosen experts
only those this chip holds add to the sum; the vocabulary is the chip's
slice), in the program and here alike. The routed sum is the plain one,
every held expert on every row (``reference_afmoe`` says why).

``alter`` plants a fault or a lower precision for the controls of
``checks_sdar_moe`` (``mask: "causal"``: the causal mask in the
block-causal one's place; ``router_dtype``: the router's product in a
lower precision where the configuration says float32; ``qk_norm:
False``).
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm, _rope)
from harness.reference_dots3_note import (_row_blocks, _swiglu,
                                          rounding_walk)  # noqa: F401


def _attend(q, k_prev, v_prev, k_own, v_own, pos0, block: int, alter=None):
    """``q`` ``[R, H, d]``: rows at positions ``pos0 ..`` (``pos0`` and
    ``R`` whole blocks). A row reads ``k_prev`` / ``v_prev`` ``[S, G,
    d]`` (positions ``0 ..``) in the blocks BELOW its own, and ``k_own``
    / ``v_own`` ``[R, G, d]`` (its own stream's rows) inside its block.
    Returns ``[R, H d]``."""
    import jax
    import jax.numpy as jnp

    causal = (alter or {}).get("mask") == "causal"
    r, h, d = q.shape
    s, g = k_prev.shape[:2]
    scale = d ** -0.5
    rb = _row_blocks(r)
    if rb % block:
        raise ValueError(f"{r} rows in blocks of {block}")
    nb = rb // block

    def one_kv_head(args):
        qg, kp, vp, ko, vo = args     # [R, H/G, d], [S, d] x 2, [R, d] x 2

        def rows(rargs):
            qb, r0 = rargs                                  # [rb, H/G, d]
            at = pos0 + r0 + jnp.arange(rb)
            below = (jnp.arange(s)[None, :] // block) < (at[:, None] // block)
            sp = jnp.where(below[None], jnp.einsum("sgd,td->gst", qb, kp)
                           * scale, -jnp.inf)               # [H/G, rb, S]
            kb = jax.lax.dynamic_slice_in_dim(ko, r0, rb).reshape(
                nb, block, d)
            vb = jax.lax.dynamic_slice_in_dim(vo, r0, rb).reshape(
                nb, block, d)
            so = jnp.einsum("bigd,bjd->gbij",
                            qb.reshape(nb, block, h // g, d), kb) * scale
            if causal:
                i = jnp.arange(block)
                so = jnp.where((i[None, :] <= i[:, None])[None, None], so,
                               -jnp.inf)
            w = jax.nn.softmax(jnp.concatenate(
                [sp, so.reshape(h // g, rb, block)], axis=-1), axis=-1)
            return (jnp.einsum("gst,td->sgd", w[..., :s], vp)
                    + jnp.einsum("gbij,bjd->bigd",
                                 w[..., s:].reshape(h // g, nb, block, block),
                                 vb).reshape(rb, h // g, d))

        return jax.lax.map(rows, (qg.reshape(r // rb, rb, h // g, d),
                                  jnp.arange(r // rb) * rb)).reshape(
            r, h // g, d)

    out = jax.lax.map(one_kv_head, (
        jnp.moveaxis(q.reshape(r, g, h // g, d), 1, 0),
        jnp.moveaxis(k_prev, 1, 0), jnp.moveaxis(v_prev, 1, 0),
        jnp.moveaxis(k_own, 1, 0), jnp.moveaxis(v_own, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(r, h * d)


def qkv(y, lp, arch: Dict[str, Any], quant: Dict[str, Any], pos0,
        alter=None):
    """q ``[R, H, d]``, k and v ``[R, G, d]`` of the normed rows ``y`` at
    positions ``pos0 ..``: projections, the per-head norm, rotary."""
    import jax.numpy as jnp

    h, g, d = int(arch["heads"]), int(arch["kv_heads"]), int(arch["head_dim"])
    eps = float(arch["norm_eps"])
    r = y.shape[0]
    pos = pos0 + jnp.arange(r)
    q = (y @ _dense(lp["q_proj"], quant)).reshape(r, h, d)
    k = (y @ _dense(lp["k_proj"], quant)).reshape(r, g, d)
    v = (y @ _dense(lp["v_proj"], quant)).reshape(r, g, d)
    if (alter or {}).get("qk_norm", True):
        q = _rms_norm(q, lp["q_norm"], eps)
        k = _rms_norm(k, lp["k_norm"], eps)
    theta = float(arch["theta"])
    return _rope(q, pos, theta, d, False), _rope(k, pos, theta, d, False), v


def attention(y, lp, arch: Dict[str, Any], quant: Dict[str, Any],
              alter=None):
    """One layer's attention on ONE stream's normed ``y`` ``[S, D]``
    under the block-causal mask, before the residual."""
    q, k, v = qkv(y, lp, arch, quant, 0, alter)
    o = _attend(q, k, v, k, v, 0, int(arch["block"]), alter)
    return o @ _dense(lp["o_proj"], quant)


def route(h, lp, arch: Dict[str, Any], alter=None):
    """Routing weights ``[S, experts_total]`` float32 of the normed rows
    ``h``, 0 where an expert is not chosen: softmax over all the
    experts, the top ``experts_per_tok``, renormalised."""
    import jax
    import jax.numpy as jnp

    dtype = (alter or {}).get("router_dtype")
    w = lp["router"].astype(jnp.float32)
    if dtype is not None:
        # rounded where the type would round it: operands and product
        # (`reduce_precision`: a compiler is free to drop a cast there
        # and back, and the CPU's does)
        fi = jnp.finfo(dtype)
        rnd = lambda x: jax.lax.reduce_precision(x, fi.nexp,   # noqa: E731
                                                 fi.nmant)
        logits = rnd(rnd(h) @ rnd(w))
    else:
        logits = h @ w
    scores = jax.nn.softmax(logits, axis=-1)
    k = int(arch["experts_per_tok"])
    topv, topi = jax.lax.top_k(scores, k)
    if arch.get("norm_topk_prob", True) and k > 1:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    return jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], topi].set(topv)


def feed_forward(h, lp, experts, arch: Dict[str, Any], quant: Dict[str, Any],
                 alter=None, share=None):
    """The held experts' part of the routed sum on the normed ``h``
    ``[S, D]`` (``experts``: this layer's stacks; ``share``:
    ``(first_held, held)`` in the place of the configuration's), in the
    plain form: every held expert on every row, times the row's weight
    for it (0 where the row did not choose it)."""
    import jax
    import jax.numpy as jnp

    first, held = share or (int(arch["first_held"]), int(arch["held"]))
    weights = route(h, lp, arch, alter)[:, first:first + held]

    def one(acc, args):            # the experts one at a time, summed
        w_col, gate, up, down = args
        return acc + w_col[:, None] * _swiglu(
            h, _dense(gate, quant), _dense(up, quant),
            _dense(down, quant)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        weights.T, experts["experts_gate"], experts["experts_up"],
        experts["experts_down"]))
    return routed


def layer_stack(params: Dict[str, Any], arch: Dict[str, Any]):
    """``(index, that layer's leaves, its routed experts)`` in the
    model's order, out of the canonical tree's stacks."""
    import jax

    for i in range(int(arch["layers"])):
        yield (i, jax.tree.map(lambda a, i=i: a[i], params["layers"]),
               jax.tree.map(lambda a, i=i: a[i], params["experts"]))


def layer(xs, lp, ex, arch, quant, start, alter=None):
    """One decoder layer on the streams ``xs``: ``xs[0]`` ``[S, D]`` the
    final sequence, every other ``[G, D]`` a noised copy of the stretch
    from position ``start`` on, which reads stream 0's keys below a
    row's block and its own inside it."""
    eps = float(arch["norm_eps"])
    block = int(arch["block"])
    ys = [_rms_norm(x, lp["input_layernorm"], eps) for x in xs]
    q0, k0, v0 = qkv(ys[0], lp, arch, quant, 0, alter)
    heads = [_attend(q0, k0, v0, k0, v0, 0, block, alter)]
    for y in ys[1:]:
        q, k, v = qkv(y, lp, arch, quant, start, alter)
        heads.append(_attend(q, k0, v0, k, v, start, block, alter))
    o_proj = _dense(lp["o_proj"], quant)
    out = []
    for x, o in zip(xs, heads):
        x = x + o @ o_proj
        out.append(x + feed_forward(
            _rms_norm(x, lp["post_attention_layernorm"], eps), lp, ex, arch,
            quant, alter))
    return tuple(out)


def embed(params: Dict[str, Any], token_ids):
    import jax.numpy as jnp

    return params["embed_tokens"][jnp.asarray(token_ids, jnp.int32)].astype(
        jnp.float32)


def _whole_blocks(n: int, arch: Dict[str, Any], what: str) -> None:
    if n % int(arch["block"]):
        raise ValueError(
            f"{what}: {n} positions are no whole blocks of "
            f"{arch['block']} (a row sees its whole block: pad from a "
            "block's edge)")


def _layers(params, arch, quant, xs, start, alter):
    import jax

    step = jax.jit(lambda xs, lp, ex, start: layer(xs, lp, ex, arch, quant,
                                                   start, alter))
    for _, lp, ex in layer_stack(params, arch):
        xs = step(xs, lp, ex, start)
    return xs


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, alter=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, ONE pass under the block-causal
    mask, on the canonical tree ``params``; row i is of the token AT
    position i. ``token_ids`` are whole blocks.

    A tree marked ``refused`` (``weights_sdar_moe.canonical_params``:
    the program was outside a limit of ``checks_sdar_moe``) is vouched
    for by no logits: they come back NaN."""
    import jax
    import jax.numpy as jnp

    ids = [int(t) for t in token_ids]
    _whole_blocks(len(ids), arch, "all_logits")
    eps = float(arch["norm_eps"])
    with jax.default_matmul_precision("highest"):
        (x,) = _layers(params, arch, quant, (embed(params, ids),),
                       jnp.int32(0), alter)
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(x, norm, eps)
                       @ _dense(lm_head, quant))
        logits = head(x[first:], params["norm"], params["lm_head"])
        return logits * jnp.nan if params.get("refused") else logits


def owed(s: int, block: int, passes: int) -> int:
    """``n_s``, the count a block's denoise pass ``s`` (0 ..) owes:
    ``B // T``, one more for the first ``B % T`` passes."""
    return block // passes + (1 if s < block % passes else 0)


def transfer(confidence, n_s: int, rule: str, threshold: float):
    """The rows a denoise pass commits, of the rows still MASK whose
    confidences (``x0_p``, the probability of the token sampled for the
    row) are ``confidence``, in sequence order: indices into it. At most
    ``min(n_s, rows)`` by count (the first departure under ``assumed``).
    ``sequential``: the first; ``low_confidence_static``: the most
    confident; ``low_confidence_dynamic``: every row over ``threshold``
    if they are at least that many, else the most confident."""
    import numpy as np

    conf = np.asarray(confidence, np.float64)
    n = min(int(n_s), conf.size)
    if rule == "sequential":
        return list(range(n))
    if rule not in ("low_confidence_static", "low_confidence_dynamic"):
        raise ValueError(f"remasking_strategy {rule!r}")
    over = [int(i) for i in np.flatnonzero(conf > threshold)]
    if rule == "low_confidence_dynamic" and len(over) >= n:
        return over
    return sorted(int(i) for i in np.argsort(-conf, kind="stable")[:n])


def replay(params: Dict[str, Any], arch: Dict[str, Any],
           quant: Dict[str, Any], final_ids: Sequence[int], noised,
           start: int, targets, alter=None) -> Dict[str, Any]:
    """What each noised stream's logits say of the served tokens and of
    the rows a pass chose, for the ``C`` streams ``noised`` ``[C, G]``
    (ids of the positions ``start .. start + G - 1``, MASK where a pass
    had committed nothing yet) beside the final sequence ``final_ids``
    ``[S]``; every value ``[C, G]``:

    - ``gap``: what the served token (``targets``, ``[G]`` or ``[C,
      G]``) lies below the row's best logit, in standard deviations of
      the row's logits;
    - ``confidence``: the log of the probability of the row's best
      token (``x0_p`` at temperature 0: softmax of the raw logits), what
      the transfer rule orders the MASK rows by;
    - ``spread``: that standard deviation, in logits;
    - ``best``: the row's best token.

    The MASK id's logit is no candidate (the program samples with it at
    -inf) and is left out of all four. NaN on a ``refused`` tree."""
    import jax
    import jax.numpy as jnp

    final = [int(t) for t in final_ids]
    noised = jnp.asarray(noised, jnp.int32)
    _whole_blocks(len(final), arch, "replay")
    _whole_blocks(int(noised.shape[1]), arch, "replay's stretch")
    _whole_blocks(int(start), arch, "replay's start")
    eps, mask_id = float(arch["norm_eps"]), int(arch["mask_token_id"])
    with jax.default_matmul_precision("highest"):
        xs = _layers(params, arch, quant,
                     (embed(params, final),)
                     + tuple(embed(params, n) for n in noised),
                     jnp.int32(start), alter)

        @jax.jit
        def read(x, norm, lm_head, tgt):
            lg = _rms_norm(x, norm, eps) @ _dense(lm_head, quant)
            keep = jnp.arange(lg.shape[-1]) != mask_id
            n = keep.sum()
            mean = jnp.where(keep, lg, 0.0).sum(-1, keepdims=True) / n
            std = jnp.sqrt(jnp.where(keep, (lg - mean) ** 2, 0.0).sum(-1) / n)
            cand = jnp.where(keep, lg, -jnp.inf)
            best = cand.max(-1)
            chosen = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
            return {"gap": (best - chosen) / jnp.maximum(std, 1e-30),
                    "confidence": best - jax.nn.logsumexp(cand, axis=-1),
                    "spread": std,
                    "best": cand.argmax(-1).astype(jnp.int32)}

        tgt = jnp.broadcast_to(jnp.asarray(targets, jnp.int32), noised.shape)
        rows = [read(x, params["norm"], params["lm_head"], t)
                for x, t in zip(xs[1:], tgt)]
        out = {k: jnp.stack([r[k] for r in rows]) for k in rows[0]}
        if params.get("refused"):
            out = {k: v * jnp.nan if k != "best" else v
                   for k, v in out.items()}
        return out


LOGITS_LIMIT = 0.1


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end (check (a): the prefill's last row
    and two noised blocks through the cache, all 48 layers). Between its
    two readings at the published widths (PERF.md 6, PR 53, second
    round; my chip runs): the program reads 0.0094-0.0134 over 20 runs
    at 20 seeds (the router's coin did not move it: an eighth expert
    swapped in one layer of 48 is lost in the sum), a CAUSAL mask in the
    block-causal one's place reads 0.689-0.729 (``checks_sdar_moe.
    generation_controls``, three seeds). 0.1 leaves seven times of room on
    both sides. What it CANNOT fail: the router's product in bfloat16
    reads 0.0012-0.0050 through this check, under the program's own
    rounding (every activation of the program is bfloat16); that
    precision is held by ``layer_limits``' ``ffn_*`` on the reference's
    own input (0.035-0.061 there against a sound 0.0045). The tiny
    preset's toy layers read inside the same bound (the CPU tests)."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 0.3, "decode_gap_max": 0.3,
                     "decode_gap_mean": 0.05}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads over four of the window's
    own greedy requests, each token's gap (which token, plus which row:
    ``generation_sdar_moe.served_gaps``) read at the pass that committed
    it; ``first`` (``prefill_gap_max``) holds the tokens of a request's
    FIRST pass: a prefill yields none. Each between its two readings at
    the published widths (PERF.md 6, PR 53, second round; my chip runs):

    - ``decode_gap_mean`` 0.05: sound 0.0001-0.0038 over 19 runs; a
      causal mask in the program's place 0.679-0.745, tokens that are
      not the model's 3.74 (``generation_controls``' ``causal_mask`` and
      ``wrong_token``): thirteen times of room on both sides;
    - ``decode_gap_max`` 0.3: sound 0.026-0.072 (the widest of 1,400 to
      2,600 tokens a run); the causal control 0.90-1.65, ONE wrong token
      of a run about 4 (the widest of 255: 6.6): three to four times of
      room on both sides;
    - ``prefill_gap_max`` 0.3: sound 0-0.041; a wrong token 4.06. The
      causal control is weak here (0.05-0.80 on two tokens): a first
      pass sees MASK in every row of its block under either mask.

    What they CANNOT fail on seeded weights: the MASK rows of a block
    share one embedding and lie within 0.04 deviations of each other in
    confidence, closer than the program lies to this reference, so the
    row gap of a program that commits the FIRST rows reads 0.0013 (the
    real program under ``sequential``, one run) where the sound ones
    read 0.0010-0.0038. At toy widths the rows lie far apart and the
    same control reads 0.022-0.032 against 0.00005-0.00016 (the CPU
    test). On the chip the rule is held by ``layer_limits``'
    ``transfer_apart``, on logits whose confidences lie far apart. A
    configuration's own ``served_gap_limits`` (the tiny preset's, whose
    mean parts that control) take their place."""
    del kv_cache_dtype
    return dict(config.get("served_gap_limits") or SERVED_GAP_LIMITS)


LAYER_LIMITS = {"attention_prefill": 0.02, "attention_block": 0.02,
                "ffn_prefill": 0.015, "ffn_block": 0.015,
                "transfer_apart": 0.05}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_sdar_moe.layer_check`` reads: the
    relative L2 of one block's output against this reference's on the
    same bfloat16 input (a prompt's chunks, then block passes through
    the cache), and the share of seeded blocks on which the engine's
    sampler and transfer rule choose otherwise than this module's. A
    configuration's own ``layer_limits`` (the tiny preset's) take their
    place. Attention 0.02 and feed-forward 0.015 are the window-and-full
    family's values (``reference_mimo_v2.layer_limits``). Readings at
    the published widths, 1,024 + 256 rows (PERF.md 6 and 7, 36 e; my
    chip runs, PR 53): sound 0.0050-0.0056 attention and 0.0045 feed-
    forward over ten runs; ``causal_mask`` 0.372-0.382 / 0.034-0.036
    (prefill, block), ``no_qk_norm`` 0.337-0.376 / 0.367-0.417,
    ``router_bf16`` 0.061-0.069 / 0.035-0.066 feed-forward.
    ``transfer_apart`` 0.05: the program reads 0 in every run (the same
    rows and tokens on each of 512 blocks), the engine's own
    ``sequential`` rule in the configured one's place 0.541 (0.72 at
    toy sizes)."""
    return dict(config.get("layer_limits") or LAYER_LIMITS)
