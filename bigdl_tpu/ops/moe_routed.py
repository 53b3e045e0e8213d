"""The routed expert layer that knows its share.

An expert-parallel deployment gives each chip some of a layer's routed
experts. This layer is told `(experts_total, first_held, held)`: it
routes every token over ALL `experts_total` experts exactly as the
model publishes, and computes the part of the weighted sum that its own
`held` experts (`first_held .. first_held + held - 1`) give. What the
other chips' experts would add is left out; with `held == total` it is
the whole layer. There is no stand-in for absent chips or their
exchange. The caller adds what every chip computes alike (shared
experts, the residual).

Routing (`route`): `softmax` scores in float32, then plain top-k
(`greedy`) or DeepSeek-V2's `group_limited_greedy`: the experts form
`n_group` groups of consecutive experts, a group scores its best
expert, the best `topk_group` groups stay, and the top-k is taken among
their experts. Weights are the chosen scores, renormalised
(`norm_topk_prob`) or times `routed_scaling_factor`. With `scoring`
"sigmoid" the scores are `sigmoid(logits)`, and `noaux_tc` (DeepSeek-V3's
bias-corrected choice) takes the top-k of `scores + bias`, a per-expert
correction that chooses and does not weigh: the weights are the chosen
experts' own scores, renormalised (`norm_topk_prob`) and times
`routed_scaling_factor`. Over several groups (`n_group` > 1) a group
scores the sum of its two largest biased scores, the best `topk_group`
groups stay and the top-k is taken among their experts.

Two strategies by token count (`ops/pallas/moe_routed.py`), no host
sync, no data-dependent shape:

- up to `DECODE_MAX_TOKENS` tokens: every hit expert multiplies all the
  tokens, in two calls: `moe_routed_decode_gate_up` (gate and up of an
  expert in one sweep, `act(g) * u` times the combine weights from the
  float32 accumulators) and `moe_routed_decode_down` (the down
  projection, summed over the experts inside the call); an expert no
  token chose is skipped, bytes and all, and a hit expert is a grid step
  or a few (`decode_tiles`: tiles by bytes under a stated VMEM budget);
- above: the sorted ragged dispatch over the held experts
  (`moe_routed_prefill`, one call a matrix).

Where the kernel is not the designed choice (no TPU, a shape that does
not tile, operands sharded under GSPMD) the dense combine in XLA ops
runs: every held expert on every token, weighted by the routing.

`RoutedStats` counts, for the `/metrics` counters: token-expert choices
by whether the expert is held here, and held experts with at least one
token.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from bigdl_tpu.ops.quant import QTensor

DECODE_MAX_TOKENS = 64
# order of the int32 counters a forward accumulates (KVCache.stats)
STATS = ("assignments_held", "assignments_not_held", "experts_hit",
         "layer_steps")


class Share(NamedTuple):
    """Which of a layer's routed experts this chip holds."""
    experts_total: int
    first_held: int
    held: int


def route(logits: jax.Array, top_k: int, *, n_group: int = 1,
          topk_group: int = 1, method: str = "greedy",
          scaling_factor: float = 1.0, norm_topk_prob: bool = False,
          scoring: str = "softmax", bias=None):
    """Router logits `[N, E]` -> (expert ids `[N, k]` int32, weights
    `[N, k]` float32). `bias` `[E]`: `noaux_tc`'s correction."""
    if scoring == "softmax":
        scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    else:
        raise NotImplementedError(f"scoring_func {scoring!r}")
    n, e = scores.shape
    if method == "noaux_tc":
        choice = scores + bias.astype(jnp.float32)
        if n_group != 1:
            # DeepSeek-V3's node-limited choice: a group scores the sum
            # of its two best biased scores, the best `topk_group` groups
            # stay, and the top-k is taken among their experts
            per = choice.reshape(n, n_group, e // n_group)
            group_score = jnp.sum(lax.top_k(per, 2)[0], axis=-1)
            _, gi = lax.top_k(group_score, topk_group)          # [N, tg]
            keep = jnp.zeros((n, n_group), bool).at[
                jnp.arange(n)[:, None], gi].set(True)
            choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1),
                               choice, -jnp.inf)
        _, topi = lax.top_k(choice, top_k)
        topv = jnp.take_along_axis(scores, topi, axis=-1)
        if norm_topk_prob and top_k > 1:
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
        return topi.astype(jnp.int32), topv * scaling_factor
    choice = scores
    if method == "group_limited_greedy":
        group_best = scores.reshape(n, n_group, e // n_group).max(axis=-1)
        _, gi = lax.top_k(group_best, topk_group)               # [N, tg]
        keep = jnp.zeros((n, n_group), bool).at[
            jnp.arange(n)[:, None], gi].set(True)
        choice = jnp.where(jnp.repeat(keep, e // n_group, axis=1),
                           scores, 0.0)
    elif method != "greedy":
        raise NotImplementedError(f"topk_method {method!r}")
    topv, topi = lax.top_k(choice, top_k)
    if norm_topk_prob and top_k > 1:
        topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + 1e-20)
    else:
        topv = topv * scaling_factor
    return topi.astype(jnp.int32), topv


def _combine(topi, topw, share: Share):
    """`[N, held]` float32: the routing weight of each held expert for
    each token (0 where the token did not choose it), and the mask of
    choices that fall on held experts."""
    local = topi - share.first_held
    mine = (local >= 0) & (local < share.held)
    onehot = jax.nn.one_hot(jnp.where(mine, local, share.held), share.held,
                            dtype=jnp.float32)                  # [N, k, held]
    return jnp.sum(onehot * topw[..., None], axis=1), mine


def _kernel_ok(name, stacks, d, ff, t) -> bool:
    """Whether the Pallas kernels are the designed choice here, probing
    what they would run: the decode pair, or the prefill call at each
    (K, N)."""
    from bigdl_tpu.config import flags, target_is_tpu, under_spmd
    from bigdl_tpu.ops.pallas.moe_routed import (DECODE_NAME,
                                                 routed_decode_compiles,
                                                 routed_kernel_compiles)

    leaves = [a for s in stacks for a in jax.tree_util.tree_leaves(s)]
    if flags().moe_dispatch == "dense" or under_spmd(*leaves):
        return False
    for s in stacks:
        if isinstance(s, QTensor) and (s.qtype != "sym_int4"
                                       or s.data.dtype != jnp.uint8):
            return False
    if not target_is_tpu():
        return flags().moe_dispatch == "ragged"     # forced: interpret
    gate, up, down = stacks
    qn = lambda s: s.qtype if isinstance(s, QTensor) else None  # noqa: E731
    if name == DECODE_NAME:
        ok = (qn(gate) == qn(up)
              and routed_decode_compiles(qn(gate), qn(down), d, ff, t))
    else:
        ok = all(routed_kernel_compiles(name, qn(s), kk, nn, t)
                 for s, kk, nn in ((gate, d, ff), (up, d, ff),
                                   (down, ff, d)))
    if not ok:
        from bigdl_tpu.ops.probing import record_dispatch_rule

        record_dispatch_rule(name)
    return ok


def _decode(xf, comb, hit, gate, up, down, layer, act, interpret):
    """Every hit expert over all the tokens; tiles are held experts,
    hit ones first. Two calls: gate and up with the activation and the
    combine weights, then down summed over the experts."""
    from bigdl_tpu.ops.pallas.moe_routed import (routed_down_sum,
                                                 routed_gate_up)

    n = xf.shape[0]
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)
    n_hit = jnp.sum(hit).astype(jnp.int32)
    npad = -(-n // 16) * 16
    x1 = jnp.pad(xf, ((0, npad - n), (0, 0)))[None]             # [1, Np, D]
    cw = jnp.pad(comb.T[order], ((0, 0), (0, npad - n)))        # [held, Np]
    h = routed_gate_up(x1, gate, up, cw, order, n_hit, layer, act=act,
                       interpret=interpret)                     # [held,Np,F]
    y = routed_down_sum(h, down, order, n_hit, layer, interpret=interpret)
    return y[:n].astype(xf.dtype)


def _prefill(xf, topi, topw, mine, share, gate, up, down, layer, act,
             interpret):
    """Sorted ragged dispatch over the held experts (the scheme of
    `ops/pallas/moe_dispatch.moe_mlp_ragged`, and its plan): choices of
    experts held elsewhere sort last and take no row. Token rows reach
    the buffer, and expert outputs the tokens, by gather."""
    from bigdl_tpu.ops.pallas.moe_dispatch import (ragged_plan,
                                                   ragged_rows_in,
                                                   ragged_rows_out)
    from bigdl_tpu.ops.pallas.moe_routed import (PREFILL_NAME,
                                                 PREFILL_TOKEN_TILE,
                                                 routed_expert_matmul)

    held, t = share.held, PREFILL_TOKEN_TILE
    flat_e = jnp.where(mine, topi - share.first_held, held).reshape(-1)
    plan = ragged_plan(flat_e, topi.shape[1], held, t)
    xt = ragged_rows_in(xf, plan).reshape(-1, t, xf.shape[1])
    mm = lambda x, w: routed_expert_matmul(                     # noqa: E731
        x, w, plan.tile_expert, plan.n_active, layer, name=PREFILL_NAME,
        interpret=interpret)
    live = (jnp.arange(xt.shape[0]) < plan.n_active)[:, None, None]
    h = jnp.where(live, act(mm(xt, gate).astype(jnp.float32))
                  * mm(xt, up).astype(jnp.float32), 0.0).astype(xf.dtype)
    # rows of tiles past `n_active` are never written, and never read:
    # a choice held here has its row in an active tile
    y = mm(h, down).reshape(-1, xf.shape[1])
    return ragged_rows_out(y, plan, topw, mine).astype(xf.dtype)


def _dense(xf, comb, gate, up, down, layer, act):
    """XLA ops: every held expert on every token, weighted."""
    from bigdl_tpu.ops.matmul import linear

    if layer is not None:
        gate, up, down = (jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
            s) for s in (gate, up, down))

    def one(gw, uw, dw):
        return linear(act(linear(xf, gw)) * linear(xf, uw), dw)

    ys = jax.vmap(one)(gate, up, down)                          # [held, N, D]
    return jnp.einsum("end,ne->nd", ys.astype(jnp.float32),
                      comb).astype(xf.dtype)


def routed_experts(xf: jax.Array, router_logits: jax.Array,
                   stacks: Dict[str, Any], share: Share, *, top_k: int,
                   act, layer=None, **routing):
    """The held experts' part of the routed sum for tokens `xf` `[N, D]`:
    `sum_i w_i * expert_{e_i}(x)` over the chosen experts that are held
    here. `stacks`: `experts_gate` / `experts_up` `[held, D, F]` and
    `experts_down` `[held, F, D]` (QTensor or dense); with `layer`, each
    has a leading layer axis and that layer is read where it lies (the
    kernels take the stack and the index). Returns the
    `[N, D]` partial result and this call's `STATS` increments."""
    gate, up, down = (stacks["experts_gate"], stacks["experts_up"],
                      stacks["experts_down"])
    n, d = xf.shape
    ff = (gate.shape[-1] if not isinstance(gate, QTensor)
          else gate.data.shape[-1])
    topi, topw = route(router_logits, top_k, **routing)
    comb, mine = _combine(topi, topw, share)
    n_mine = jnp.sum(mine).astype(jnp.int32)
    hit = jnp.any(comb != 0.0, axis=0)                          # [held]
    stats = jnp.stack([
        n_mine, jnp.int32(n * top_k) - n_mine,
        jnp.sum(hit).astype(jnp.int32), jnp.int32(1)])
    at = 0 if layer is None else layer
    from bigdl_tpu.config import target_is_tpu

    interpret = not target_is_tpu()
    if n <= DECODE_MAX_TOKENS:
        from bigdl_tpu.ops.pallas.moe_routed import DECODE_NAME

        if _kernel_ok(DECODE_NAME, (gate, up, down), d, ff,
                      -(-n // 16) * 16):
            return _decode(xf, comb, hit, gate, up, down, at, act,
                           interpret), stats
    else:
        from bigdl_tpu.ops.pallas.moe_routed import (PREFILL_NAME,
                                                     PREFILL_TOKEN_TILE)

        if _kernel_ok(PREFILL_NAME, (gate, up, down), d, ff,
                      PREFILL_TOKEN_TILE):
            return _prefill(xf, topi, topw, mine, share, gate, up, down,
                            at, act, interpret), stats
    return _dense(xf, comb, gate, up, down, layer, act), stats
