"""EVA chunked linearized attention over a cache of two kinds of plane
(`models/evabyte.py` runs it; Zheng et al., ICLR 2023).

A query at position `i` attends, in ONE softmax, the exact keys of its
own window up to itself (`w(j) = j // window` equal to `w(i)`, `j <= i`)
and one learned SUMMARY a chunk of `stride` positions of every earlier
window (chunks `c < w(i) * window / stride`). The summary of a chunk with
positions `C`: `alpha_j = softmax_{j in C} scale * (k_j . phi_h)`, `k~ =
sum_j alpha_j k_j + mu_h`, `v~ = sum_j alpha_j v_j`, per head `h`, on the
roped keys as the cache holds them.

The cache (`ops/kvcache.py`): `win_k` / `win_v` `[L, B, window, H, hd]`,
position `p` in column `p % window`, live columns `0 .. p % window`;
`sum_k` / `sum_v` `[L, B, S / stride, H, hd]`, chunk `c` in column `c`
(`mu` already added to the key). Every function takes the whole stacks
and the layer's index.

Two paths. One decoded row a slot on a TPU: the slab's row append
(`kvcache.update_layer` at `pos % window`), `eva_summarize` (rewrites
column `pos // stride` from the chunk's live rows, every step and
branch-free: the last write of a chunk is the whole chunk's) and
`eva_decode_attention` (both planes in one online softmax, reading only
what is live), `ops/pallas/eva_attention.py`. Everything else (a chunk of
a prompt; any call off the TPU) is `_row_attention` below in XLA ops: it
takes any number of rows from any position, windows crossed or not, the
chunk's scores in groups of heads so that `[H, rows, keys]` never exists
in float32 at once.
"""

from __future__ import annotations

import warnings
from typing import Dict, Sequence

import jax
import jax.numpy as jnp

_NEG = -1e30
# heads whose scores a chunk holds at once: 8 x 1024 rows x 3584 keys in
# float32 is 117 MB at the published widths
_HEAD_GROUP = 8


def rows_read(positions: Sequence[int], window: int, stride: int
              ) -> Dict[str, int]:
    """Rows of ONE layer the decode kernel's rule names for queries at
    `positions` (plain ints, each the query's own position): `window`
    exact keys, `summary` rows, and `context`, what full attention would
    read. For the engine's counter."""
    per_window = window // stride
    return {"window": sum(p % window + 1 for p in positions),
            "summary": sum(p // window * per_window for p in positions),
            "context": sum(p + 1 for p in positions)}


def summarize_rows(k, v, live, phi, mu, scale: float):
    """Summaries of chunks given as rows: `k`, `v` `[..., n, H, hd]`,
    `live` `[..., n]` (rows that exist), `phi`, `mu` `[H, hd]`. Returns
    float32 `(k~, v~)` `[..., H, hd]`; a chunk with no live row gives
    `mu` and zeros."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    logit = jnp.einsum("...nhd,hd->...nh", kf, phi.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST) * scale
    logit = jnp.where(live[..., None], logit, _NEG)
    p = jnp.where(live[..., None],
                  jnp.exp(logit - jnp.max(logit, axis=-2, keepdims=True)),
                  0.0)
    alpha = p / jnp.maximum(jnp.sum(p, axis=-2, keepdims=True), 1e-30)
    k_sum = jnp.sum(alpha[..., None] * kf, axis=-3) + mu.astype(jnp.float32)
    v_sum = jnp.sum(alpha[..., None] * vf, axis=-3)
    return k_sum, v_sum


def _place(rows, n: int, at):
    """`rows` `[m, ...]` as an `[n, ...]` array whose row `(at + t) % n`
    is `rows[t]` (m <= n; the other rows are padding)."""
    m = rows.shape[0]
    if m < n:
        rows = jnp.concatenate(
            [rows, jnp.zeros((n - m,) + rows.shape[1:], rows.dtype)])
    return jnp.roll(rows, at, axis=0)


def _row_attention(q, kn, vn, wk, wv, sk, sv, a, live_row, phi, mu, *,
                   scale: float, stride: int):
    """One batch row. `q`, `kn`, `vn` `[S, H, hd]`: the rows at positions
    `a .. a + S - 1`; `wk`, `wv` `[W, H, hd]` and `sk`, `sv` `[Ns, H,
    hd]` the row's planes of one layer as they were BEFORE these rows.
    `live_row` False: a slot that holds nothing (output zeros). Returns
    the attention output `[S, H, hd]` float32 and the four planes with
    the rows and the summaries they touch written."""
    s, h, hd = q.shape
    w, ns, c = wk.shape[0], sk.shape[0], stride
    kn, vn = kn.astype(wk.dtype), vn.astype(wv.dtype)
    a = jnp.asarray(a, jnp.int32)

    # -- summaries of the chunks these rows touch: the chunk's earlier
    # rows come from the window plane (same chunk, so same window)
    r = a % c
    a0 = a - r
    n_c = (s + c - 1) // c + 1
    t = jnp.arange(n_c * c, dtype=jnp.int32)
    src = jnp.clip(t - r, 0, s - 1)

    def extended(new, plane):
        old = jax.lax.dynamic_slice_in_dim(plane, a0 % w, c, axis=0)
        return jnp.where((t < r)[:, None, None],
                         old[jnp.minimum(t, c - 1)], new[src])

    there = t < r + s
    k_sum, v_sum = summarize_rows(
        extended(kn, wk).reshape(n_c, c, h, hd),
        extended(vn, wv).reshape(n_c, c, h, hd),
        there.reshape(n_c, c), phi, mu, scale)
    col = jnp.arange(ns, dtype=jnp.int32)
    c0 = a0 // c
    wrote = (col >= c0) & (col < c0 + n_c) & (col * c < a + s)
    keep = min(n_c, ns)
    sk2 = jnp.where(wrote[:, None, None],
                    _place(k_sum[:keep].astype(sk.dtype), ns, c0), sk)
    sv2 = jnp.where(wrote[:, None, None],
                    _place(v_sum[:keep].astype(sv.dtype), ns, c0), sv)

    # -- the window plane takes the last `window` of the rows
    m = min(s, w)
    a1 = a + (s - m)
    wcol = jnp.arange(w, dtype=jnp.int32)
    fresh = jnp.mod(wcol - a1, w) < m
    wk2 = jnp.where(fresh[:, None, None], _place(kn[s - m:], w, a1 % w), wk)
    wv2 = jnp.where(fresh[:, None, None], _place(vn[s - m:], w, a1 % w), wv)

    # -- one softmax over [old window columns, the new rows, summaries]
    pq = a + jnp.arange(s, dtype=jnp.int32)               # [S]
    wq = pq // w
    old_ok = (wcol[None, :] < a % w) & (wq[:, None] == a // w)
    new_ok = ((wq[:, None] == wq[None, :])
              & (pq[None, :] <= pq[:, None]))
    sum_ok = col[None, :] < (wq * (w // c))[:, None]
    ok = jnp.concatenate([old_ok, new_ok, sum_ok], axis=1) & live_row
    keys = jnp.concatenate([wk, kn, sk2]).astype(jnp.float32)
    vals = jnp.concatenate([wv, vn, sv2]).astype(jnp.float32)
    qf = q.astype(jnp.float32) * scale

    def heads(args):
        qg, kg, vg = args                   # [G, S, hd], [G, T, hd] x 2
        sc = jnp.einsum("gsd,gtd->gst", qg, kg,
                        preferred_element_type=jnp.float32)
        sc = jnp.where(ok[None], sc, _NEG)
        p = jnp.where(ok[None],
                      jnp.exp(sc - jnp.max(sc, axis=-1, keepdims=True)), 0.0)
        den = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
        return jnp.einsum("gst,gtd->gsd", p / den, vg,
                          preferred_element_type=jnp.float32)

    g = _HEAD_GROUP if h % _HEAD_GROUP == 0 else h

    def grouped(x):                         # [T, H, hd] -> [H/G, G, T, hd]
        return jnp.moveaxis(x, 1, 0).reshape(h // g, g, x.shape[0], hd)

    out = jax.lax.map(heads, (grouped(qf), grouped(keys), grouped(vals)))
    return (jnp.moveaxis(out.reshape(h, s, hd), 0, 1), wk2, wv2, sk2, sv2)


def _attend_xla(q, kn, vn, win_k, win_v, sum_k, sum_v, layer, pos,
                phi, mu, scale: float, stride: int):
    b = q.shape[0]
    posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    stacks = (win_k, win_v, sum_k, sum_v)
    out, *planes = jax.vmap(
        lambda *xs: _row_attention(*xs, phi, mu, scale=scale,
                                   stride=stride))(
        q, kn, vn,
        *(jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
          for x in stacks),
        jnp.maximum(posv, 0), posv >= 0)
    return (out, *(jax.lax.dynamic_update_index_in_dim(x, p, layer, 0)
                   for x, p in zip(stacks, planes)))


def eva_attention(q, kn, vn, win_k, win_v, sum_k, sum_v, layer, pos,
                  phi, mu, *, scale: float, stride: int):
    """The attention of one layer for the rows `q`, `kn`, `vn` `[B, S, H,
    hd]` (roped) at `pos` (scalar, or `[B]` per slot; below 0: a slot
    that holds nothing) over layer `layer` (an int or a traced int32 scalar) of the four
    stacks. Returns the output `[B, S, H, hd]` in `q`'s type and the
    stacks with the rows appended and the summaries they touch
    rewritten."""
    from bigdl_tpu.config import target_is_tpu
    from bigdl_tpu.ops.kvcache import update_layer
    from bigdl_tpu.ops.pallas import eva_attention as kernels

    b, s = q.shape[:2]
    decode_on_tpu = s == 1 and target_is_tpu()
    if decode_on_tpu and not kernels.geometry_ok(q, win_k, sum_k, stride):
        # said once (the default filter): such a deployment serves, but
        # its decode step is float32 XLA ops and the kernels' metrics
        # read nothing
        warnings.warn(
            f"chunked linearized attention: the decode kernels do not take "
            f"this geometry (head size {q.shape[-1]}, window "
            f"{win_k.shape[2]}, {sum_k.shape[2]} summary columns, chunk "
            f"{stride}, planes {win_k.dtype}; they need a head size and "
            f"both planes in multiples of 128, a chunk in multiples of 8 "
            f"and bf16 planes): decode runs in XLA ops", RuntimeWarning,
            stacklevel=2)
        decode_on_tpu = False
    if decode_on_tpu:
        posv = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1),
                                (b,))
        window = win_k.shape[2]
        with jax.named_scope("eva.summarize"):
            win_k, win_v = update_layer(win_k, win_v, layer, kn, vn,
                                        jnp.maximum(posv, 0) % window)
            sum_k, sum_v = kernels.eva_summarize_pallas(
                win_k, win_v, sum_k, sum_v, posv, phi, mu, scale=scale,
                stride=stride, layer=layer)
        with jax.named_scope("eva.attend"):
            out = kernels.eva_decode_attention_pallas(
                q, win_k, win_v, sum_k, sum_v, posv, scale=scale,
                stride=stride, layer=layer)
        return out, win_k, win_v, sum_k, sum_v
    with jax.named_scope("eva.attend"):
        out, win_k, win_v, sum_k, sum_v = _attend_xla(
            q, kn, vn, win_k, win_v, sum_k, sum_v, layer, pos, phi, mu,
            scale, stride)
    return out.astype(q.dtype), win_k, win_v, sum_k, sum_v
