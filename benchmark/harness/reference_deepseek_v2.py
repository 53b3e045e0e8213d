"""The plain reference of DeepSeek-V2: a forward pass in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``,
one layer at a time, NOT absorbed (K and V are materialised per head
from the compressed rows), no kernel, no cache, no batching, on weights
dequantized by plain arithmetic. No import of the program.

The layer, as published (HF ``modeling_deepseek.py``), told by the
``reference`` block of the configuration's file (``arch``):

- attention: ``c_q = RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> per head
  ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c_kv <-
  RMSNorm(c_kv)``, ``k_pe`` one head for all and not normed; rope on
  ``q_pe`` and ``k_pe`` only, channels 2i and 2i+1 rotating together
  (HF de-interleaves before ``rotate_half``: the same rotation with the
  output channels permuted alike in q and k, so every dot product is the
  same); YaRN frequencies (linear ramp between ``beta_fast`` and
  ``beta_slow`` rotations over the original context), cos and sin times
  ``mscale(factor, mscale) / mscale(factor, mscale_all_dim)``;
  ``[k_nope | v] = c_kv W_kvb`` per head; scores ``(q_nope . k_nope +
  q_pe . k_pe) * (nope + rope)^-0.5 * mscale(factor, mscale_all_dim)^2``
  with ``mscale(f, m) = 0.1 m ln f + 1``; causal softmax; output
  ``concat_h(softmax . v) W_o``;
- feed-forward: the first ``first_k_dense`` layers a dense SwiGLU; the
  others the shared experts (one SwiGLU of ``n_shared x moe width``) plus
  ``sum_i w_i SwiGLU^(e_i)(x)``: scores ``softmax(x W_g)`` in float32
  over ``experts_total``, ``group_limited_greedy`` (``n_group`` groups
  of consecutive experts, a group scores its best expert, the best
  ``topk_group`` groups stay, the best ``experts_per_tok`` among their
  experts are taken), ``w_i = routed_scaling_factor * s_i`` (or
  renormalised with ``norm_topk_prob``).

Departures from the published model: weights are the seeded random
block-quantized planes the program serves, dequantized here to float32
as ``(code - 8) * scale``; and the configuration's SHARE: of the chosen
experts only those this chip holds (``first_held .. first_held + held -
1``) add to the sum, in the program and here alike (``model-configs``
guide, section 4); with ``held == experts_total`` it is the whole
layer. Attention runs over groups of heads and the experts one at a
time so that a 4,096-token sequence fits at the published widths.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

from harness.reference import (next_token_loss, relative_l2,  # noqa: F401
                               unpack_sym_int4, _dense, _rms_norm)

HEAD_GROUP = 8        # heads whose [S, S] scores are live together


def yarn_inv_freq(rope: Dict[str, Any], dim: int):
    """YaRN's inverse frequencies ``[dim // 2]``."""
    import jax.numpy as jnp

    base, factor = float(rope["theta"]), float(rope.get("factor", 1.0))
    half = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / base ** half
    if factor <= 1.0:
        return extra
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(rope["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / factor * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1.0 else 0.1 * m * math.log(factor) + 1.0


def _rope(x, positions, inv_freq, magnitude: float):
    """x ``[S, H, rd]``: channels 2i and 2i+1 rotate together."""
    import jax.numpy as jnp

    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = (jnp.cos(ang) * magnitude)[:, None, :]
    sin = (jnp.sin(ang) * magnitude)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def route(scores, arch: Dict[str, Any]):
    """Softmax scores ``[S, E]`` -> weights ``[S, E]`` float32: the
    routing weight of each expert for each token, 0 where not chosen."""
    import jax
    import jax.numpy as jnp

    s, e = scores.shape
    k, groups = int(arch["experts_per_tok"]), int(arch["n_group"])
    choice = scores
    if arch.get("topk_method", "group_limited_greedy") == \
            "group_limited_greedy":
        best = scores.reshape(s, groups, e // groups).max(axis=-1)
        # a group stays where its best score is among the topk_group best
        _, gi = jax.lax.top_k(best, int(arch["topk_group"]))
        keep = jnp.zeros((s, groups), bool).at[
            jnp.arange(s)[:, None], gi].set(True)
        choice = jnp.where(jnp.repeat(keep, e // groups, axis=1), scores, 0.0)
    topv, topi = jax.lax.top_k(choice, k)
    if arch.get("norm_topk_prob") and k > 1:
        topv = topv / (topv.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        topv = topv * float(arch["routed_scaling_factor"])
    return jnp.zeros((s, e), jnp.float32).at[
        jnp.arange(s)[:, None], topi].set(topv)


def attention(y, lp, arch, quant, latent_dtype=None):
    """MLA on the normed ``y`` ``[S, D]``, K and V per head. With
    ``latent_dtype`` the rows a latent cache would hold (the normed
    ``c_kv``, the roped ``k_pe``) are rounded to that type first: the
    control, a cache in a precision below the configuration's."""
    import jax
    import jax.numpy as jnp

    h, c = int(arch["heads"]), int(arch["kv_lora_rank"])
    nope, r = int(arch["qk_nope_head_dim"]), int(arch["qk_rope_head_dim"])
    vd, eps = int(arch["v_head_dim"]), float(arch["norm_eps"])
    rope = arch["rope"]
    s = y.shape[0]
    pos = jnp.arange(s)
    if "q_proj" in lp:
        q = y @ _dense(lp["q_proj"], quant)
    else:
        q = _rms_norm(y @ _dense(lp["q_a_proj"], quant),
                      lp["q_a_layernorm"], eps) @ _dense(lp["q_b_proj"],
                                                         quant)
    q = q.reshape(s, h, nope + r)
    kv = y @ _dense(lp["kv_a_proj"], quant)
    c_kv = _rms_norm(kv[:, :c], lp["kv_a_layernorm"], eps)
    factor = float(rope.get("factor", 1.0))
    inv_freq = yarn_inv_freq(rope, r)
    magnitude = (yarn_mscale(factor, float(rope.get("mscale", 1.0)))
                 / yarn_mscale(factor, float(rope.get("mscale_all_dim", 0.0))))
    q_pe = _rope(q[..., nope:], pos, inv_freq, magnitude)
    k_pe = _rope(kv[:, None, c:], pos, inv_freq, magnitude)[:, 0]   # [S, r]
    if latent_dtype is not None:
        c_kv = c_kv.astype(latent_dtype).astype(jnp.float32)
        k_pe = k_pe.astype(latent_dtype).astype(jnp.float32)
    kvb = (c_kv @ _dense(lp["kv_b_proj"], quant)).reshape(s, h, nope + vd)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    scale = (nope + r) ** -0.5
    all_dim = float(rope.get("mscale_all_dim", 0.0))
    if all_dim:
        scale *= yarn_mscale(factor, all_dim) ** 2
    allowed = pos[None, :] <= pos[:, None]

    def heads(args):
        qn, qp, kn, vv = args          # [S, g, .] of one group of heads
        scores = (jnp.einsum("shd,thd->hst", qn, kn)
                  + jnp.einsum("shr,tr->hst", qp, k_pe)) * scale
        probs = jax.nn.softmax(jnp.where(allowed[None], scores, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hst,thd->shd", probs, vv)

    g = math.gcd(h, HEAD_GROUP)
    split = lambda a: jnp.moveaxis(                           # noqa: E731
        a.reshape(s, h // g, g, a.shape[-1]), 1, 0)
    out = jax.lax.map(heads, (split(q[..., :nope]), split(q_pe),
                              split(k_nope), split(v)))       # [h/g, S, g, vd]
    attn = jnp.moveaxis(out, 0, 1).reshape(s, h * vd)
    return attn @ _dense(lp["o_proj"], quant)


def _swiglu(y, gate, up, down):
    import jax

    return (jax.nn.silu(y @ gate) * (y @ up)) @ down


def _moe(y, lp, arch, quant):
    """Shared experts plus the held experts' part of the routed sum."""
    import jax
    import jax.numpy as jnp

    scores = jax.nn.softmax(y @ lp["router"].astype(jnp.float32), axis=-1)
    first, held = int(arch["first_held"]), int(arch["held"])
    weights = route(scores, arch)[:, first:first + held]         # [S, held]

    def one(args):
        w_col, gate, up, down = args
        return w_col[:, None] * _swiglu(y, _dense(gate, quant),
                                        _dense(up, quant),
                                        _dense(down, quant))

    parts = jax.lax.map(one, (weights.T, lp["experts_gate"],
                              lp["experts_up"], lp["experts_down"]))
    shared = _swiglu(y, _dense(lp["shared_gate"], quant),
                     _dense(lp["shared_up"], quant),
                     _dense(lp["shared_down"], quant))
    return shared + parts.sum(axis=0)


def feed_forward(h, lp, arch: Dict[str, Any], quant: Dict[str, Any]):
    """The feed-forward block on the normed ``h`` ``[S, D]``: dense where
    ``lp`` holds ``gate_proj``, shared plus held routed experts where
    it holds ``router``."""
    if "router" in lp:
        return _moe(h, lp, arch, quant)
    return _swiglu(h, _dense(lp["gate_proj"], quant),
                   _dense(lp["up_proj"], quant),
                   _dense(lp["down_proj"], quant))


def layer(x, lp, arch: Dict[str, Any], quant: Dict[str, Any],
          latent_dtype=None):
    """One decoder layer on ``x`` ``[S, D]`` float32."""
    eps = float(arch["norm_eps"])
    x = x + attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch,
                      quant, latent_dtype)
    return x + feed_forward(
        _rms_norm(x, lp["post_attention_layernorm"], eps), lp, arch, quant)


def layer_stack(params: Dict[str, Any]):
    """``(group, index, that layer's leaves)`` in the model's order."""
    import jax

    for group in ("dense_layers", "moe_layers"):
        layers = params.get(group)
        if not layers:
            continue
        for i in range(layers["input_layernorm"].shape[0]):
            yield group, i, jax.tree.map(lambda a, i=i: a[i], layers)


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0, latent_dtype=None):
    """Float32 logits ``[S - first, V]`` of the positions of
    ``token_ids`` from ``first`` on, on the canonical tree ``params``
    (``dense_layers`` then ``moe_layers``, each stacked over its layers,
    ``kv_b_proj`` one linear). ``latent_dtype``: ``attention``'s.

    A tree marked ``refused`` (``weights_deepseek_v2.canonical_params``:
    the program was outside a limit of ``checks_deepseek_v2``, layer by
    layer on the reference's own inputs) is vouched for by no logits:
    they come back NaN, so that every comparison the harness makes with
    them reads not correct. The harness's ``checks`` take no entry of a
    configuration's own; this is how the layer check reaches them."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        step = jax.jit(lambda x, lp: layer(x, lp, arch, quant, latent_dtype))
        ids = jnp.asarray(list(token_ids), jnp.int32)
        x = params["embed_tokens"][ids].astype(jnp.float32)
        for _, _, lp in layer_stack(params):
            x = step(x, lp)
        head = jax.jit(lambda x, norm, lm_head: _rms_norm(
            x, norm, float(arch["norm_eps"])) @ _dense(lm_head, quant))
        logits = head(x[first:], params["norm"], params["lm_head"])
        return logits * jnp.nan if params.get("refused") else logits


LOGITS_LIMIT = 1.2


def rounding_walk(layers: int) -> float:
    """How far bfloat16 rounding alone carries the program's logits from
    this reference's while no expert is swapped: about twelve tensors a
    layer rounded at 2**-9, walking randomly through the depth, times
    the 2.4 the dense configurations measured between that estimate and
    the chip (``reference.tolerance``): 0.073 at 20 layers. The CPU
    tests hold the program to it at toy widths, where the router's
    scores lie far apart."""
    return 2.4 * 2.0 ** -9 * math.sqrt(12.0 * layers)


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end. It tells logits that are the
    model's from logits that are not (unrelated rows read 1.41), and
    nothing finer: what holds the program to a precision is
    ``layer_limits``.

    Why nothing finer. Routing is discontinuous. The program rounds to
    bfloat16 where the reference keeps float32, so its hidden state
    walks away from the reference's by about 2**-9 * sqrt(12 roundings *
    layers), 1-3 % (the dense configurations' walk,
    ``reference.tolerance``). At the published initializer_range the
    router's logits have a standard deviation of 0.02 * sqrt(5120) =
    1.43, the sixth and seventh of a token's 60 candidates lie 0.13
    apart on average and the third and fourth of its 8 groups 0.3-0.5:
    a state 1-3 % off moves a logit by 0.02-0.04, which swaps an expert
    at many token-layers and a whole group (every held expert of the
    token at once, weights of about 1 beside shared experts of weight
    1) at about one position in ten. From there on the two sides are
    different functions of the token. Readings (my chip runs, PR 28,
    second round, published widths, 20 layers, N(0, 0.02) throughout,
    four seeds, 36 positions): 26 positions 0.039-0.13, seven 0.13-0.37,
    three 0.49-0.58, none higher; prefill's one position 0.041, 0.052,
    0.054, 0.369; the 8 decoded positions together 0.057, 0.213, 0.228,
    0.236 (four runs of the cell: 0.074-0.387 and 0.134-0.332). The
    reference with its latent rows in float8_e5m2 in the
    program's place (a perturbation ten times the program's) reads
    0.30-0.73 and 0.49-0.58, single positions up to 0.956: the same
    coin, thrown more often, and no limit between the two would hold
    over the seeds a check draws. 1.2 lies twice over the largest sound
    position and under 1.41; every sound reading above is a reading of
    the coin, not of the program."""
    del config, kv_cache_dtype
    return LOGITS_LIMIT


SERVED_GAP_LIMITS = {"prefill_gap_max": 9.0, "decode_gap_max": 9.0,
                     "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads (how far the token the
    engine streamed lies under the reference's best logit at its
    position, in standard deviations of the position's logits, over four
    of the window's own greedy requests, 3,800-4,700 served tokens).
    Where the router's coin fell differently (``tolerance``) the
    program's best token is some other token of the reference, at a few
    positions any token; a token from a wrong row, page, position or
    slot is a random one everywhere, 3.9 deviations down in the mean
    over 12,800 logits. The MEAN gap is what tells them apart: sound
    0.069-0.078 (my chip runs, PR 28, second round, four runs of the
    cell at N(0, 0.02)); wrong rows in one request of the four sampled
    1.0, in all four 3.9; its limit 0.5. The widest gap cannot tell a
    swapped expert from a wrong token: sound it reads 3.30-4.09 among
    the decoded tokens (0-0.48 among the four first tokens), which is
    the reach of a random token already, and it is bounded by the
    logits' range (largest less smallest: 7.8 deviations over 12,800
    normal logits). Its limit of 9 lies past that range and says so:
    in this configuration the widest gap decides nothing."""
    del config, kv_cache_dtype
    return dict(SERVED_GAP_LIMITS)


LAYER_LIMITS = {"attention_prefill": 0.022, "attention_decode": 0.022,
                "ffn_prefill": 0.015, "ffn_decode": 0.015}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_deepseek_v2.layer_check`` reads: the
    relative L2 of one block's output against this reference's on the
    same bfloat16 input, the largest over the layers. No expert can be
    swapped there, so these are the limits that hold the program to its
    precision and to its mathematics.

    Readings (my chip runs, PR 28, second round, published widths, 20
    layers, a 256-row chunk and 8 decoded rows through a 4096-position
    slab; sound: eight seeds, four in a diagnostic call and four runs of
    the cell; every later run: PERF.md 6, PR 28):

    - attention, prefill rows / decoded rows: sound 0.00621-0.00631 /
      0.00680-0.00694 (layer 0, whose input is the embedding; the
      others 0.0043-0.0050). Control, the latent rows in float8_e5m2,
      the precision below the configuration's bfloat16 (four seeds):
      0.0725-0.0729 / 0.0800-0.0810. Limit 0.022: 3.2 times the largest
      sound reading, 3.3 times under the smallest of the control.
    - feed-forward, prefill rows / decoded rows: sound 0.00413-0.00421 /
      0.00461-0.00477. Planted faults on the chip (one seed): the factor
      16 left out 0.634 / 0.731, the routed sum left out 0.676 / 0.780,
      another rank's expert indices 0.874 / 1.506, each held expert
      taken for its neighbour 0.946 / 1.165. Limit 0.015: 3.1 times the
      largest sound reading, 42 times under the smallest fault; a
      routed sum wrong in a tenth of its weight reads about 0.07.

    The limits do not depend on the depth: every layer is compared on
    its own."""
    del config
    return dict(LAYER_LIMITS)
