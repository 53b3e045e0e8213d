"""Microbatched pipeline parallelism over the `pp` mesh axis.

The reference's "pipeline parallel" is a naive 2-GPU layer split with no
microbatching — `accelerate.dispatch_model` over a device_map (reference
example/GPU/Pipeline-Parallel-Inference/generate.py:44-62): one GPU idles
while the other computes. This module is the real schedule the reference
lacks: a GPipe-style microbatched pipeline expressed the TPU way —

- The stacked layer tree [L, ...] is sharded along L over the `pp` axis
  (each stage holds L/P contiguous layers — works for dense AND quantized
  stacks, since every QTensor field is [L, ...]-leading).
- The schedule is a `lax.scan` over M + P - 1 ticks inside `shard_map`;
  activations move stage→stage with `lax.ppermute` over ICI. Stage 0
  injects a fresh microbatch each tick; the last stage's outputs fill in
  as the pipeline drains. Bubble fraction = (P-1)/(M+P-1), the GPipe
  formula — pick M >= 4*P to amortize.
- Reverse-mode AD flows through scan+ppermute (ppermute transposes to the
  reverse permutation), so the same schedule backs `make_pp_train_step` —
  1F1B-style memory scheduling is left to XLA's rematerialization
  (`jax.checkpoint` on the per-layer body).

Composes with the other axes: dp shards each microbatch's rows, tp shards
the within-layer matmuls (GSPMD), pp moves whole-layer activations.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.models import llama as M


def pp_param_specs(params: Dict[str, Any]) -> Dict[str, Any]:
    """PartitionSpec tree: layer stacks split along L over `pp`, the rest
    replicated."""
    specs = {k: jax.tree.map(lambda _: P(), v)
             for k, v in params.items() if k != "layers"}
    specs["layers"] = jax.tree.map(lambda _: P("pp"), params["layers"])
    return specs


def shard_params_pp(params: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Place the parameter tree: [L, ...] leaves split over `pp`."""
    pp = mesh.shape["pp"]
    sample = jax.tree_util.tree_leaves(params["layers"])[0]
    if sample.shape[0] % pp != 0:
        raise ValueError(
            f"num_hidden_layers {sample.shape[0]} not divisible by pp={pp}")
    specs = pp_param_specs(params)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def _stage_forward(x, layers_local, cfg, cos, sin, slopes, stage, lp_count):
    """Run this stage's local layer stack on one microbatch activation."""
    lidx0 = stage * lp_count

    @jax.checkpoint
    def layer(x, xs):
        lp, li = xs
        out, _ = M._decoder_layer(x, lp, cfg, cos, sin, slopes,
                                  cache_ctx=None, lidx=li)
        return out

    lids = lidx0 + jnp.arange(lp_count, dtype=jnp.int32)
    x, _ = lax.scan(lambda c, xs: (layer(c, xs), None), x,
                    (layers_local, lids))
    return x


def pp_forward_train(
    params: Dict[str, Any],
    cfg,
    tokens: jax.Array,            # [B, S] int32
    mesh: Mesh,
    num_microbatches: int,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Cacheless causal forward under the pipeline schedule.

    Returns logits [B, S, V] (valid on every device — the last stage's
    result is broadcast, so downstream loss code is placement-agnostic).
    Use `make_pp_train_step` for training (it keeps the loss scalar
    instead of broadcasting full logits).
    """
    return _pp_apply(params, cfg, tokens, mesh, num_microbatches,
                     compute_dtype, want="logits")


def _pp_apply(params, cfg, tokens, mesh, num_microbatches, compute_dtype,
              want="logits", targets=None, mask=None):
    pp = mesh.shape["pp"]
    L = cfg.num_hidden_layers
    if L % pp != 0:
        raise ValueError(f"num_hidden_layers {L} not divisible by pp={pp}")
    lp_count = L // pp
    b, s = tokens.shape
    mcount = num_microbatches
    if b % mcount != 0:
        raise ValueError(f"batch {b} not divisible by microbatches {mcount}")
    mb = b // mcount

    inv_freq, rope_mscale = M.model_rope_freqs(cfg)
    positions = jnp.arange(s, dtype=jnp.int32)
    from bigdl_tpu.ops.rope import rope_cos_sin

    cos, sin = rope_cos_sin(positions[None, :], inv_freq)
    if rope_mscale != 1.0:
        cos, sin = cos * rope_mscale, sin * rope_mscale
    slopes = (jnp.asarray(M.alibi_slopes(cfg.num_attention_heads))
              if cfg.use_alibi else None)

    top = {k: v for k, v in params.items() if k != "layers"}
    args = [top, params["layers"], tokens]
    specs = [jax.tree.map(lambda _: P(), top),
             jax.tree.map(lambda _: P("pp"), params["layers"]), P()]
    if targets is not None:
        args += [targets, mask]
        specs += [P(), P()]

    def body(top, layers_local, tokens, *rest):
        stage = lax.axis_index("pp")
        micro = tokens.reshape(mcount, mb, s)
        ticks = mcount + pp - 1
        perm = [(i, (i + 1) % pp) for i in range(pp)]

        def embed(toks):
            return M.embed_prologue(top, cfg, toks, positions,
                                    compute_dtype)

        d = cfg.hidden_size

        def tick(carry, t):
            x_recv = carry                       # from previous stage
            inj = embed(micro[jnp.minimum(t, mcount - 1)])
            x_in = jnp.where(stage == 0, inj, x_recv)
            y = _stage_forward(x_in, layers_local, cfg, cos, sin, slopes,
                               stage, lp_count)
            x_next = lax.ppermute(y, "pp", perm)
            return x_next, y

        x0 = jnp.zeros((mb, s, d), compute_dtype)
        _, ys = lax.scan(tick, x0, jnp.arange(ticks))

        # last stage's emissions at ticks P-1 .. P-2+M are microbatches
        # 0..M-1; other stages' slots are pipeline garbage
        outs = ys[pp - 1:].reshape(b, s, d)
        hidden = M._norm(outs, top["norm"], top.get("norm_bias"), cfg)
        logits = M._lm_head(hidden, top, cfg)
        is_last = (stage == pp - 1).astype(logits.dtype)

        if want == "loss":
            targets_, mask_ = rest
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(
                logp, targets_[..., None].astype(jnp.int32), axis=-1
            )[..., 0]
            m = mask_.astype(jnp.float32)
            local = jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
            # only the final stage computed real activations
            return lax.psum(local * is_last, "pp")
        return lax.psum(logits * is_last, "pp")

    fn = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                       out_specs=P(), check_vma=False)
    return fn(*args)


def make_pp_train_step(cfg, mesh: Mesh, optimizer,
                       num_microbatches: int,
                       compute_dtype=jnp.bfloat16):
    """jit-compiled (params, opt_state, batch) -> (params, opt_state, loss)
    under the pipeline schedule. `batch` = {"tokens": [B, S+1] int32,
    "mask": [B, S+1]} (next-token loss, like training.make_train_step).
    Gradients stay stage-local (same [L,...]-split sharding as params);
    the optimizer update runs shard-wise under GSPMD.
    """

    def loss_fn(params, tokens, targets, mask):
        return _pp_apply(params, cfg, tokens, mesh, num_microbatches,
                         compute_dtype, want="loss", targets=targets,
                         mask=mask)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, batch):
        toks = batch["tokens"]
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(toks)
        tokens, targets = toks[:, :-1], toks[:, 1:]
        m = mask[:, 1:]
        loss, grads = jax.value_and_grad(loss_fn)(params, tokens, targets,
                                                  m)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step


def pp_generate_forward(
    params: Dict[str, Any],
    cfg,
    tokens: jax.Array,
    mesh: Mesh,
    num_microbatches: int = 1,
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """Inference convenience: pipeline-parallel scoring of a batch of
    prompts (the reference's Pipeline-Parallel-Inference example shape —
    layer-split forward — but microbatched instead of lock-step).
    Decode-with-KV-cache under pp is intentionally not provided: on TPU
    meshes, tensor parallelism over ICI dominates for token-by-token
    decoding (PARITY.md §2.2); pp targets whole-sequence throughput."""
    return pp_forward_train(params, cfg, tokens, mesh, num_microbatches,
                            compute_dtype)
