"""The plain reference of EvaByte: a forward pass in straightforward
``jax.numpy`` and float32 under ``default_matmul_precision("highest")``,
one layer at a time, no kernel, no cache, no batching, on weights
dequantized by plain arithmetic. No import of the program.

The layer, told by the ``reference`` block of the configuration's file
(``arch``; 32 equal pre-norm layers, the residual in float32):

- ``RMSNorm(x) = x / rms(x) * (1 + g)``, eps ``norm_eps``;
- ``q, k, v = y W_q, y W_k, y W_v``, ``heads`` heads of ``head_dim``,
  rope (channel i with i + head_dim / 2, base ``theta``, no scaling) on
  all of ``q`` and ``k``; ``s = head_dim ** -0.5``;
- EVA attention, window ``W = window``, chunk ``c = chunk``, per head
  two learned vectors ``phi`` (``adaptive_phi``) and ``mu``
  (``adaptive_mu_k``). Summary of chunk ``c`` (positions ``C``):
  ``alpha_j = softmax_{j in C} s (k_j . phi)``, ``k~ = sum alpha_j k_j +
  mu``, ``v~ = sum alpha_j v_j``. Query ``i`` attends in ONE softmax the
  keys ``j`` of its own window (``j // W == i // W``, ``j <= i``) and the
  summaries of the chunks of every earlier window (``c < (i // W) * W /
  c``); then ``W_o``;
- SwiGLU ``W_down(silu(y W_gate) * y W_up)``;
- head: final norm, ``h W_head``, ``W_head`` ``[D, pred_heads * vocab]``;
  head ``p`` is columns ``p * vocab .. (p + 1) * vocab``. ``all_logits``
  returns head 0 (the contract's ``V``: what the engine samples);
  ``all_head_logits`` every column.

Departures from the published model: weights are the seeded random
block-quantized planes the program serves, dequantized here as ``(code -
8) * scale``; nothing else. Products that are zero by the model's own
definition are not made: a window's rows meet the keys of their window
and the summaries before it (``attention``; ``attention_masked`` is the
plain masked form over every key and every summary, which a test holds
it to at a small size), one window and ``HEAD_GROUP`` heads at a time,
so that 8,192 rows fit beside the weights at the published widths.

``alter`` plants a fault or a lower precision for the controls of
``checks_evabyte`` (``summaries: False``, ``sliding: True``, ``mu:
False``, ``kv_dtype``).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Sequence

from harness.reference import (_dense, relative_l2,  # noqa: F401
                               next_token_loss, unpack_sym_int4)

HEAD_GROUP = 8        # heads whose [rows, keys] scores are live together


def _rms_norm(x, g, eps: float):
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x, positions, theta: float):
    """x ``[S, H, hd]`` float32, the whole head, rotate-half."""
    import jax.numpy as jnp

    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def qkv(y, lp, arch: Dict[str, Any], quant: Dict[str, Any], alter=None):
    """Roped ``q, k`` and ``v`` ``[S, H, hd]`` of the normed rows ``y``."""
    import jax.numpy as jnp

    s = y.shape[0]
    h, hd = int(arch["heads"]), int(arch["head_dim"])
    pos = jnp.arange(s)
    theta = float(arch["theta"])
    q = _rope((y @ _dense(lp["q_proj"], quant)).reshape(s, h, hd), pos,
              theta)
    k = _rope((y @ _dense(lp["k_proj"], quant)).reshape(s, h, hd), pos,
              theta)
    v = (y @ _dense(lp["v_proj"], quant)).reshape(s, h, hd)
    dt = (alter or {}).get("kv_dtype")
    if dt is not None:          # a cache of a lower precision
        k = k.astype(dt).astype(jnp.float32)
        v = v.astype(dt).astype(jnp.float32)
    return q, k, v


def summaries(k, v, lp, arch: Dict[str, Any], alter=None):
    """``(k~, v~)`` ``[S // c, H, hd]`` of the complete chunks of ``k``,
    ``v`` ``[S, H, hd]``."""
    import jax
    import jax.numpy as jnp

    alter = alter or {}
    c, hd = int(arch["chunk"]), k.shape[-1]
    n = k.shape[0] // c
    kc = k[:n * c].reshape(n, c, *k.shape[1:])
    vc = v[:n * c].reshape(n, c, *v.shape[1:])
    phi = lp["adaptive_phi"].astype(jnp.float32)
    alpha = jax.nn.softmax(
        jnp.einsum("nchd,hd->nch", kc, phi) * hd ** -0.5, axis=1)
    k_sum = jnp.einsum("nch,nchd->nhd", alpha, kc)
    if alter.get("mu", True):
        k_sum = k_sum + lp["adaptive_mu_k"].astype(jnp.float32)
    v_sum = jnp.einsum("nch,nchd->nhd", alpha, vc)
    dt = alter.get("kv_dtype")
    if dt is not None:
        k_sum = k_sum.astype(dt).astype(jnp.float32)
        v_sum = v_sum.astype(dt).astype(jnp.float32)
    return k_sum, v_sum


def _softmax_over(q, keys, vals, ok):
    """``q`` ``[R, G, hd]`` against ``keys``, ``vals`` ``[T, G, hd]``
    where ``ok`` ``[R, T]``: ``[R, G, hd]``."""
    import jax
    import jax.numpy as jnp

    sc = jnp.einsum("rgd,tgd->grt", q, keys) / math.sqrt(q.shape[-1])
    sc = jnp.where(ok[None], sc, -jnp.inf)
    return jnp.einsum("grt,tgd->rgd", jax.nn.softmax(sc, axis=-1), vals)


def attention(y, lp, arch: Dict[str, Any], quant: Dict[str, Any],
              alter=None, probe=None):
    """The attention of one layer on the normed rows ``y`` ``[S, D]``
    (positions 0 .. S - 1), before the residual: ``[S, D]``. ``probe``
    (a dict) receives the summaries."""
    import jax.numpy as jnp

    alter = alter or {}
    s = y.shape[0]
    h, hd = int(arch["heads"]), int(arch["head_dim"])
    w, c = int(arch["window"]), int(arch["chunk"])
    q, k, v = qkv(y, lp, arch, quant, alter)
    k_sum, v_sum = summaries(k, v, lp, arch, alter)
    if probe is not None:
        probe["k_sum"], probe["v_sum"] = k_sum, v_sum
    g = HEAD_GROUP if h % HEAD_GROUP == 0 else h
    if alter.get("sliding"):
        # the fault: the last W positions, whatever the window
        pos = jnp.arange(s)
        ok = ((pos[None, :] <= pos[:, None])
              & (pos[None, :] > pos[:, None] - w))
        out = jnp.concatenate(
            [_softmax_over(q[:, a:a + g], k[:, a:a + g], v[:, a:a + g], ok)
             for a in range(0, h, g)], axis=1)
        return out.reshape(s, h * hd) @ _dense(lp["o_proj"], quant)
    rows = []
    for m in range(-(-s // w)):
        lo, hi = m * w, min((m + 1) * w, s)
        n_sum = m * (w // c) if alter.get("summaries", True) else 0
        pos = jnp.arange(lo, hi)
        ok = jnp.concatenate(
            [pos[None, :] <= pos[:, None],
             jnp.ones((hi - lo, n_sum), bool)], axis=1)
        rows.append(jnp.concatenate(
            [_softmax_over(
                q[lo:hi, a:a + g],
                jnp.concatenate([k[lo:hi, a:a + g], k_sum[:n_sum, a:a + g]]),
                jnp.concatenate([v[lo:hi, a:a + g], v_sum[:n_sum, a:a + g]]),
                ok) for a in range(0, h, g)], axis=1))
    out = jnp.concatenate(rows)
    return out.reshape(s, h * hd) @ _dense(lp["o_proj"], quant)


def attention_masked(y, lp, arch: Dict[str, Any], quant: Dict[str, Any]):
    """``attention`` in the plain masked form: every query against every
    key and every summary, the definition's sets as one mask."""
    import jax.numpy as jnp

    s = y.shape[0]
    h, hd = int(arch["heads"]), int(arch["head_dim"])
    w, c = int(arch["window"]), int(arch["chunk"])
    q, k, v = qkv(y, lp, arch, quant)
    k_sum, v_sum = summaries(k, v, lp, arch)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    chunk = jnp.arange(k_sum.shape[0])[None, :]
    ok = jnp.concatenate([(j // w == i // w) & (j <= i),
                          chunk < (i // w) * (w // c)], axis=1)
    out = _softmax_over(q, jnp.concatenate([k, k_sum]),
                        jnp.concatenate([v, v_sum]), ok)
    return out.reshape(s, h * hd) @ _dense(lp["o_proj"], quant)


def feed_forward(y, lp, quant: Dict[str, Any]):
    import jax

    gate = y @ _dense(lp["gate_proj"], quant)
    up = y @ _dense(lp["up_proj"], quant)
    return (jax.nn.silu(gate) * up) @ _dense(lp["down_proj"], quant)


def head(x, norm, lm_head, arch: Dict[str, Any], quant: Dict[str, Any]):
    """Every prediction head's logits ``[S, pred_heads * vocab]``."""
    return _rms_norm(x, norm, float(arch["norm_eps"])) @ _dense(lm_head,
                                                               quant)


def _layer(x, lp, arch: Dict[str, Any], quant: Dict[str, Any]):
    eps = float(arch["norm_eps"])
    x = x + attention(_rms_norm(x, lp["input_layernorm"], eps), lp, arch,
                      quant)
    return x + feed_forward(
        _rms_norm(x, lp["post_attention_layernorm"], eps), lp, quant)


def live_rows(token_ids: Sequence[int], first: int, window: int) -> int:
    """Rows a pass has to walk: the whole windows up to the last one
    that holds a non-zero id or a position from ``first`` on that
    follows one. ``served.compare`` right-pads every request to ONE
    length with id 0; a window of nothing but zeros with nothing after
    it is that padding (no traffic file draws id 0, and an answer is
    shorter than a window, so no compared position lies in one), and a
    window attends no later one."""
    live = len(token_ids)
    while live > 0 and int(token_ids[live - 1]) == 0:
        live -= 1
    live = max(live, first + 1, 1)
    return min(len(token_ids), -(-live // window) * window)


def all_head_logits(params: Dict[str, Any], arch: Dict[str, Any],
                    quant: Dict[str, Any], token_ids: Sequence[int],
                    first: int = 0):
    """Float32 logits ``[S - first, pred_heads * vocab]`` of the
    positions of ``token_ids`` from ``first`` on, on the canonical tree
    ``params``; every position attends as the model defines, ``first``
    only spares the head on positions nobody compares. Windows of right
    padding (``live_rows``) are not walked: their rows come back zeros.
    One set of programs a number of windows, so at most
    ``len(token_ids) / window`` of them whatever the requests."""
    import jax
    import jax.numpy as jnp

    if params.get("refused"):
        raise RuntimeError("the layer check refused this tree "
                           "(checks_evabyte.layer_check)")
    ids = [int(t) for t in token_ids]
    rows = live_rows(ids, first, int(arch["window"]))
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda x, lp: _layer(x, lp, arch, quant))
        x = params["embed_tokens"][jnp.asarray(ids[:rows], jnp.int32)
                                   ].astype(jnp.float32)
        for lp in params["layers"]:
            x = layer(x, lp)
        # weights are arguments, never closed over: a closed-over array
        # is baked into the executable as a constant of its size
        out = jax.jit(lambda x, norm, lm_head: head(x, norm, lm_head, arch,
                                                    quant))
        logits = out(x[first:], params["norm"], params["lm_head"])
    return jnp.pad(logits, ((0, len(ids) - rows), (0, 0)))


def all_logits(params: Dict[str, Any], arch: Dict[str, Any],
               quant: Dict[str, Any], token_ids: Sequence[int],
               first: int = 0):
    """Head 0's float32 logits ``[S - first, vocab]``: the next byte's,
    which the engine samples from."""
    return all_head_logits(params, arch, quant, token_ids,
                           first)[:, :int(arch["vocab"])]


def tolerance(config: Dict[str, Any], kv_cache_dtype: str) -> float:
    """Bound on the program's relative L2 distance from this
    reference's logits, end to end: the dense rule of
    ``reference.tolerance`` (every layer rounds about nine tensors to
    bfloat16, walking randomly through the depth, 2.4 times the
    estimate: 0.0795 at 32 layers). The model is dense and throws no
    coin; its residual stream is float32 in the program too, so it reads
    under the dense cells (PERF.md 6, PR 39 has the readings). The cache
    is bfloat16 only."""
    del kv_cache_dtype
    layers = int(config["reference"]["layers"])
    return 2.4 * 2.0 ** -9 * math.sqrt(9.0 * layers)


SERVED_GAP_FACTORS = {"prefill_gap_max": 6.0, "decode_gap_max": 6.0,
                      "decode_gap_mean": 0.5}


def served_gap_limits(config: Dict[str, Any], kv_cache_dtype: str
                      ) -> Dict[str, float]:
    """Limits on what ``served.compare`` reads, by the dense rule
    (``reference.served_gap_limits``): the gaps go with ``tolerance``;
    six tolerances on the widest gap, half a tolerance on the mean."""
    tol = tolerance(config, kv_cache_dtype)
    return {k: f * tol for k, f in SERVED_GAP_FACTORS.items()}


# the readings behind each are in ``layer_limits``'s docstring
LAYER_LIMITS = {
    "eva_attention_prefill": 0.012, "eva_attention_decode": 0.012,
    "eva_summary_rel_l2": 0.006,
    "ffn_prefill": 0.012, "ffn_decode": 0.012,
    "head_rel_l2": 0.012,
}


def layer_limits(config: Dict[str, Any]) -> Dict[str, float]:
    """Limits on what ``checks_evabyte.layer_check`` reads: the relative
    L2 of one block's output against this reference's on the same
    bfloat16 input (``checks_evabyte`` has the rows).

    Derivation. A block rounds its input's products to bfloat16 a few
    times (q, k, v; the cache's rows; the probabilities; the output of
    each linear): each a relative 2**-9 in RMS, four to six of them
    independent: 0.004-0.005 expected; a summary is a weighted mean of
    16 rounded keys, rounded once more: 2**-9 * sqrt(2) = 0.003. The
    limits lie 2-2.5 times over that and under what the nearest lower
    precision reads (fp8_e5m2 rounds a key to 2 bits of mantissa, a
    relative 2**-3 / sqrt(3) = 0.07 on every key, value and summary).
    Readings and the controls' refusals: PERF.md 6, PR 39. A
    configuration's own ``layer_limits`` (the tiny preset's) take their
    place."""
    return dict(config.get("layer_limits") or LAYER_LIMITS)
