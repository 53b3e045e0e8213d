"""Ring attention: exact causal attention over a sequence-parallel mesh axis.

The reference has NO sequence/context parallelism (SURVEY.md §2.2: its
long-context story is FP8 KV + per-model 32k variants, all single-device).
This is the planned superset capability: shard the sequence over the `sp`
mesh axis, keep Q local, and rotate K/V chunks around the ring with
`lax.ppermute` while accumulating flash-style online softmax — peak memory
per chip is O(S/sp), communication rides ICI and overlaps with the chunk
matmuls (XLA schedules the ppermute DMA concurrently with compute).

Two layers:
- `ring_attention(q, k, v, axis_name)` — call INSIDE `shard_map` over a
  mesh with `axis_name`; q/k/v are the local sequence chunks.
- `sp_attention(q, k, v, mesh, axis)` — convenience wrapper that shard_maps
  over full arrays.

Math: online softmax accumulation in f32 (m: running row max, l: running
normalizer, o: unnormalized output), causal mask computed from *global*
positions (chunk index x chunk length + local offset). Matches
`sdp_attention` to float tolerance, verified in tests on the CPU mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P



def _chunk_scores(q, k, scale, logits_soft_cap):
    # q [B, Sq, Hkv, G, D], k [B, Sk, Hkv, D] -> [B, Hkv, G, Sq, Sk] f32
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if logits_soft_cap is not None:
        s = jnp.tanh(s / logits_soft_cap) * logits_soft_cap
    return s


def ring_attention(
    q: jax.Array,          # [B, Sq_loc, H, D] local query chunk
    k: jax.Array,          # [B, Sk_loc, Hkv, D] local key chunk
    v: jax.Array,          # [B, Sk_loc, Hkv, D]
    axis_name: str,
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
    layout: str = "contiguous",
) -> jax.Array:
    """Exact causal attention with K/V rotating around `axis_name`.

    Sequence layouts across the axis (n devices, chunk length C):
    - "contiguous": device i holds global positions [i*C, (i+1)*C) —
      the training sp layout.
    - "cyclic": device i holds positions i, i+n, i+2n, ... — the
      context-parallel INFERENCE layout (parallel/cp.py), where decode
      tokens keep landing on rotating owners so the sharded KV cache
      stays balanced at any prompt length.
    Returns [B, Sq_loc, H, D].
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    if scale is None:
        scale = d ** -0.5
    if layout not in ("contiguous", "cyclic"):
        raise ValueError(f"unknown ring layout {layout!r}")

    p = lax.axis_index(axis_name)
    n = lax.psum(1, axis_name)

    def global_ids(dev, length):
        if layout == "contiguous":
            return dev * length + jnp.arange(length, dtype=jnp.int32)
        return dev + jnp.arange(length, dtype=jnp.int32) * n

    qf = q.reshape(b, sq, hkv, g, d).astype(jnp.bfloat16)
    q_ids = global_ids(p, sq)                               # global q pos

    # Q-blocking inside each ring step: the per-step scores are
    # [B, Hkv, G, bq, Sk] — unblocked (bq = Sq) a 32k/sp=4 llama-7B
    # prefill materialized an 8.6 GB f32 score tensor per step and blew
    # past one v5e's HBM. Long local chunks process Q in sub-blocks
    # under lax.map (sequential; buffers reuse), bounding the working
    # set at ~bq x Sk while keeping the math identical (each q row's
    # online-softmax state is independent of other rows). The carry and
    # loop-invariant q blocks live in block-major layout for the whole
    # ring loop — ONE transpose in, one out.
    bq = sq
    if sq > 1024:
        # largest divisor of sq <= 1024 (not just powers of two: a
        # non-128-multiple local chunk must still block, or the OOM
        # this exists to prevent comes back for exactly those shapes)
        for cand in range(1024, 1, -1):
            if sq % cand == 0:
                bq = cand
                break
    nb = sq // bq

    # block-major: [nb, B, ...(bq)...]
    qf_bk = jnp.moveaxis(qf.reshape(b, nb, bq, hkv, g, d), 1, 0)
    ids_bk = q_ids.reshape(nb, bq)
    o0 = jnp.zeros((nb, b, hkv, g, bq, d), jnp.float32)
    m0 = jnp.full((nb, b, hkv, g, bq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((nb, b, hkv, g, bq), jnp.float32)
    # the loop body makes these device-varying (they depend on axis_index);
    # mark the initial values accordingly for shard_map's vma tracking.
    o0, m0, l0 = (lax.pcast(x, (axis_name,), to="varying")
                  for x in (o0, m0, l0))

    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (p - i) % n                                   # chunk we hold
        k_ids = global_ids(src, sk)
        kb = k_cur.astype(jnp.bfloat16)
        vb = v_cur.astype(jnp.bfloat16)

        def one_block(xs):
            qf_b, o_b, m_b, l_b, qid_b = xs
            s = _chunk_scores(qf_b, kb, scale,
                              logits_soft_cap)          # [B,Hkv,G,bq,Sk]
            mask = k_ids[None, :] <= qid_b[:, None]     # [bq, Sk]
            if sliding_window is not None:
                mask &= k_ids[None, :] > qid_b[:, None] - sliding_window
            s = jnp.where(mask[None, None, None], s, -jnp.inf)

            m_new = jnp.maximum(m_b, jnp.max(s, axis=-1))
            # fully-masked rows keep m == -inf; guard exp against NaN
            alpha = jnp.where(jnp.isfinite(m_b), jnp.exp(m_b - m_new), 0.0)
            pexp = jnp.exp(s - m_new[..., None])
            pexp = jnp.where(jnp.isfinite(s), pexp, 0.0)
            l_new = l_b * alpha + jnp.sum(pexp, axis=-1)
            o_new = o_b * alpha[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", pexp.astype(jnp.bfloat16), vb,
                preferred_element_type=jnp.float32)
            return o_new, m_new, l_new

        if nb == 1:
            o1, m1, l1 = one_block((qf_bk[0], o[0], m[0], l[0], ids_bk[0]))
            o, m, l = o1[None], m1[None], l1[None]
        else:
            o, m, l = lax.map(one_block, (qf_bk, o, m, l, ids_bk))

        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt)

    o, m, l, _, _ = lax.fori_loop(0, n, body, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l, 1e-30)[..., None]      # [nb,B,Hkv,G,bq,D]
    out = jnp.moveaxis(out, 0, 3).reshape(b, hkv, g, sq, d)
    out = jnp.moveaxis(out, 3, 1).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def sp_attention(
    q: jax.Array,          # [B, S, H, D] (global, sharded on S)
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    axis: str = "sp",
    scale: Optional[float] = None,
    logits_soft_cap: Optional[float] = None,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """shard_map wrapper: sequence-parallel exact causal attention."""
    fn = functools.partial(ring_attention, axis_name=axis, scale=scale,
                           logits_soft_cap=logits_soft_cap,
                           sliding_window=sliding_window)
    spec = P(None, axis, None, None)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)
