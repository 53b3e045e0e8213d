"""Tensor-parallel inference under EXPLICIT shard_map — kernels on shards.

The GSPMD path (parallel/sharding.py: shard the params, let XLA insert
the collectives) is correct but cannot use Pallas kernels — Mosaic ops
are not auto-partitionable (see PARITY.md "Multi-chip kernel dispatch"),
so it runs XLA ops. This module is the kernel-capable alternative, the
analog of how the reference reaches its per-device SYCL kernels through
DeepSpeed-AutoTP's explicit sharding (reference transformers/convert.py:
102-119 + dist.inference_all_reduce at low_bit_linear.py:635-637):

- the forward runs INSIDE shard_map over a 1-axis tp mesh;
- every device holds its head/column shard (q/k/v/gate/up column-split,
  o/down row-split — the same llama_param_specs layout) and computes
  with LOCAL shapes, so `sdp_attention`/`q_matmul` dispatch to the
  Pallas kernels exactly as on a single chip;
- the two row-parallel matmuls are followed by explicit `lax.psum`
  (the `inference_all_reduce` analog), the lm_head's column shards
  `all_gather` into full logits.

Families: everything the generalized decoder serves (r4 — the local
body IS `M.forward` with collective-injecting weight wrappers, so
parallel-residual, shared-input-norm, non-gated-MLP, sliding-window and
soft-cap families all work) including (r5) MoE expert stacks and ALiBi
families (each device slices the full-model slope schedule at its head
offset). Embeddings and norms are replicated (as in the reference's
AutoTP).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from bigdl_tpu.models import llama as M
from bigdl_tpu.observability.compile_watch import tracked_jit
from bigdl_tpu.ops.kvcache import KVCache
from bigdl_tpu.ops.matmul import linear
from bigdl_tpu.parallel.sharding import llama_param_specs



def _tp_cfg(cfg, n: int, axis: str = "tp"):
    # r4: the local body is the REAL generalized decoder (M.forward with
    # collective-injecting weight wrappers), so every family knob it
    # supports — parallel residual, shared input norm, non-gated MLP,
    # layernorm biases, partial rotary, sliding windows, soft caps —
    # and (r5) MoE expert stacks work under explicit TP. One exclusion
    # remains:
    if getattr(cfg, "num_local_experts", 0) \
            and cfg.intermediate_size % n:
        raise ValueError(
            f"MoE expert ff {cfg.intermediate_size} not divisible by "
            f"tp={n}: expert stacks are not lane-padded (pad_ff_for_tp "
            "covers dense MLPs only); use a dividing tp, the ep axis "
            "(models/mixtral.py), or the GSPMD path")
    if cfg.num_attention_heads % n or cfg.num_key_value_heads % n:
        raise ValueError(
            f"heads ({cfg.num_attention_heads}/{cfg.num_key_value_heads}) "
            f"not divisible by tp={n}")
    if cfg.intermediate_size % n and _ff_padded(
            cfg.intermediate_size, n) == cfg.intermediate_size:
        # big models lane-pad their way to divisibility (_ff_padded);
        # small ones must fail HERE with a named error, not deep inside
        # device_put with a shard-count message
        raise ValueError(
            f"intermediate_size {cfg.intermediate_size} not divisible "
            f"by tp={n} (model too small for lane padding)")
    return dataclasses.replace(
        cfg,
        num_attention_heads=cfg.num_attention_heads // n,
        num_key_value_heads=cfg.num_key_value_heads // n,
        # ff may be lane-padded at shard time; runtime shapes come from
        # the weights, this field is only a bookkeeping hint
        intermediate_size=cfg.intermediate_size // n
        if cfg.intermediate_size % n == 0 else cfg.intermediate_size,
        head_dim=cfg.hd,   # pin: hd otherwise derives from FULL heads
        # ALiBi slopes are a function of the FULL head count; the local
        # trace slices the full schedule at its axis_index (llama.py
        # _model_slopes)
        alibi_total_heads=(cfg.num_attention_heads
                           if cfg.use_alibi else None),
        tp_axis=axis)


def tp_param_specs(params: Any, mesh: Mesh, axis: str = "tp") -> Any:
    """Shard specs for the explicit-TP path: the standard col/row rules,
    except embeddings are REPLICATED (a vocab-sharded gather inside
    shard_map would need masked-psum index arithmetic for no win here).

    Unlike the GSPMD path — where a quantized weight's planes may shard
    inconsistently and the partitioner just handles it — the explicit
    path computes with the LOCAL arrays, so every plane of a col/row
    weight must actually split. Validates and raises otherwise (tiny
    models: block-quantized scale planes have K/32 rows; K must satisfy
    K/32 % tp == 0 for row-parallel weights)."""
    specs = llama_param_specs(params, mesh, axis=axis)
    specs = jax.tree_util.tree_map_with_path(
        lambda path, s: P() if any(
            getattr(e, "key", None) == "embed_tokens" for e in path) else s,
        specs, is_leaf=lambda x: isinstance(x, P))

    from bigdl_tpu.parallel.sharding import LLAMA_RULES, _path_param_name

    flat_s = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, P))[0]
    for path, s in flat_s:
        name = _path_param_name(path)
        style = LLAMA_RULES.get(name)
        if name == "embed_tokens" or style is None:
            continue
        if not any(ax is not None for ax in s):
            raise ValueError(
                f"explicit TP cannot shard {name!r} over {axis}="
                f"{mesh.shape[axis]}: a plane's sharded dim does not "
                "divide (block-quantized scales need K/block % tp == 0); "
                "use the GSPMD path (parallel/sharding.py) or a smaller "
                "tp for this model")
    return specs


def _ff_padded(ff: int, n: int, block: int = 128) -> int:
    """Global intermediate size padded so each tp shard's ff slice is a
    128-lane multiple AND a quant-block multiple. An unaligned shard
    (e.g. 11008/4 = 2752, which is 21.5 x 128) can never satisfy the
    Pallas matmul's bn tiling, so the whole MLP would decode on the slow
    XLA dequant path; and block-256 qtypes (k-quants,
    iqx) additionally need the down-proj's per-shard K to be a 256
    multiple, or the plane-row scaling in `_pad_ff_leaf` produces
    inconsistent shapes for odd shard counts (r4 advice). Zero-padding
    is EXACT: padded gate/up columns carry zero scales, so they
    dequantize to 0, the activation is act(0)*0 = 0, and the padded
    down-proj rows are zero too. Tiny test models stay untouched."""
    if ff < 2048 or n <= 1:
        return ff
    align = max(128, block)
    per = -(-ff // n)
    per = -(-per // align) * align
    return per * n


def _pad_axis(a, axis: int, new: int):
    pad = new - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    if isinstance(a, jax.core.Tracer) or not hasattr(a, "shape"):
        return jnp.pad(a, widths)
    # concrete values pad on HOST: jnp.pad would materialize each full
    # padded weight on device 0 before the sharded device_put, a
    # transient whole-model-on-one-chip HBM spike at load time
    return np.pad(np.asarray(a), widths)


def _pad_ff_leaf(w, ff_new: int, axis_kind: str):
    """Zero-pad one (possibly layer-stacked) weight along its ff dim.
    axis_kind "n": gate/up (+biases) — last axis. "k": down-proj — the
    K axis; every QTensor plane's row count scales proportionally."""
    import dataclasses as dc

    from bigdl_tpu.ops.quant import QTensor

    if w is None:
        return None
    if isinstance(w, QTensor):
        if axis_kind == "n":
            if w.data.shape[-1] >= ff_new:
                return w
            rep = {f: _pad_axis(getattr(w, f), -1, ff_new)
                   for f in ("data", "scale", "zero", "aux")
                   if getattr(w, f) is not None}
            return dc.replace(w, shape=(w.shape[0], ff_new), **rep)
        kp = w.scale.shape[-2] * w.qt.block_size
        if kp >= ff_new:
            return w
        assert ff_new % w.qt.block_size == 0, \
            f"ff pad {ff_new} breaks block {w.qt.block_size} alignment"
        rep = {}
        for f in ("data", "scale", "zero", "aux"):
            p = getattr(w, f)
            if p is None:
                continue
            rep[f] = _pad_axis(p, -2, p.shape[-2] * ff_new // kp)
        return dc.replace(w, shape=(ff_new, w.shape[1]), **rep)
    return _pad_axis(w, -1 if axis_kind == "n" else -2, ff_new)


def pad_ff_for_tp(params: Any, n: int) -> Any:
    """Pad the per-layer MLP weights (ff dim) and the untied lm_head
    (vocab dim) so their tp shards are lane-aligned (no-op when already
    aligned). Exact — see `_ff_padded`; padded lm_head columns carry
    zero scales and the local forward slices the gathered logits back
    to the true vocab."""
    from bigdl_tpu.ops.quant import QTensor

    layers = params.get("layers")
    new_params = params
    if isinstance(layers, dict) and "down_proj" in layers:
        gate = layers.get("gate_proj", layers.get("up_proj"))
        if gate is not None:
            ff = gate.shape[1] if isinstance(gate, QTensor) \
                else gate.shape[-1]
            down = layers["down_proj"]
            blk = down.qt.block_size if isinstance(down, QTensor) else 128
            ff_new = _ff_padded(ff, n, blk)
            if ff_new != ff:
                new_layers = dict(layers)
                for name in ("gate_proj", "up_proj",
                             "gate_proj_bias", "up_proj_bias"):
                    if layers.get(name) is not None:
                        new_layers[name] = _pad_ff_leaf(
                            layers[name], ff_new, "n")
                new_layers["down_proj"] = _pad_ff_leaf(
                    layers["down_proj"], ff_new, "k")
                new_params = {**new_params, "layers": new_layers}
    head = params.get("lm_head")
    if head is not None:
        v = head.shape[1] if isinstance(head, QTensor) else head.shape[-1]
        v_new = _ff_padded(v, n)
        if v_new != v:
            new_params = {**new_params,
                          "lm_head": _pad_ff_leaf(head, v_new, "n")}
    return new_params


def shard_params_tp(params: Any, mesh: Mesh, axis: str = "tp") -> Any:
    layers = params.get("layers", {})
    if isinstance(layers, dict) and (
            "qkv_proj" in layers or "gate_up_proj" in layers):
        # a contiguous N-shard of a merged weight interleaves q/k/v
        # (gate/up) across devices — wrong math, so refuse loudly
        raise ValueError(
            "explicit TP shards the SPLIT projection layout; load the "
            "model with merge_projections=False (or run models.llama."
            "unmerge_projections) before shard_params_tp")
    params = pad_ff_for_tp(params, mesh.shape[axis])
    specs = tp_param_specs(params, mesh, axis=axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)


def tp_cache_specs(axis: str = "tp") -> P:
    # [L, B, S, Hkv, hd]: heads sharded
    return P(None, None, None, axis, None)


def new_cache_tp(cfg, batch: int, max_seq: int, mesh: Mesh,
                 quantized=False, axis: str = "tp") -> KVCache:
    _tp_cfg(cfg, mesh.shape[axis], axis)  # fail fast, clear message
    from bigdl_tpu.ops.kvcache import (SCALED_KV_DTYPES,
                                       resolve_kv_cache_dtype)

    if resolve_kv_cache_dtype(quantized) in SCALED_KV_DTYPES:
        # the shard_mapped TP step carries only the k/v planes; the
        # int8/int4 scale planes are not threaded through its specs yet
        raise NotImplementedError(
            "kv_cache_dtype int8/int4 is not supported under explicit "
            "tensor parallelism; use 'bf16' or 'fp8_e5m2'")
    cache = M.new_cache(cfg, batch, max_seq, quantized=quantized)
    sh = NamedSharding(mesh, tp_cache_specs(axis))
    return KVCache(jax.device_put(cache.k, sh),
                   jax.device_put(cache.v, sh), cache.pos)


def _localize_qtensors(tree):
    """Inside shard_map a QTensor's ARRAYS are local shards but its
    static logical `shape` metadata still describes the global tensor —
    recompute it from the physical shards (valid because the sharding
    rules only split block-aligned dims)."""
    import dataclasses as dc

    from bigdl_tpu.ops.quant import QTensor, get_qtype

    def fix(w):
        if not isinstance(w, QTensor):
            return w
        qt = get_qtype(w.qtype)
        k_l = w.scale.shape[-2] * qt.block_size
        n_l = w.data.shape[-1]
        return dc.replace(w, shape=(min(w.shape[0], k_l), n_l))

    return jax.tree.map(fix, tree,
                        is_leaf=lambda x: not isinstance(x, (dict, list,
                                                             tuple)))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AllReduceLinear:
    """Row-parallel local weight: y = psum(x @ w_local) [+ bias].

    The collective rides the weight leaf (ops/matmul.linear dispatches
    to `apply_linear`), so the UNMODIFIED generalized decoder body runs
    per-device inside shard_map — the literal analog of DeepSpeed
    AutoTP's LinearAllreduce wrapper (`dist.inference_all_reduce`,
    reference transformers/low_bit_linear.py:635-637), expressed as a
    pytree transform instead of module surgery. The bias is replicated
    and must be added once, AFTER the reduce."""

    base: Any
    axis: str

    def apply_linear(self, x, bias, backend=None):
        y = linear(x, self.base, None, backend=backend)
        y = lax.psum(y, self.axis)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y

    def post_reduce(self, y):
        """The reduce alone — for paths that consume `.base` directly
        (the ragged MoE kernel takes the raw expert stack) and reduce
        the partial output themselves."""
        return lax.psum(y, self.axis)

    def tree_flatten(self):
        return (self.base,), (self.axis,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0])


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class AllGatherLinear:
    """Column-parallel local weight whose FULL output is needed (the
    lm_head): y = all_gather(x @ w_local)[..., :true_n] [+ bias].
    `true_n` drops zero-scale vocab-padding logits before they can win
    an argmax."""

    base: Any
    axis: str
    true_n: int

    def apply_linear(self, x, bias, backend=None):
        y = linear(x, self.base, None, backend=backend)
        y = lax.all_gather(y, self.axis, axis=y.ndim - 1, tiled=True)
        y = y[..., :self.true_n]
        if bias is not None:
            y = y + bias.astype(y.dtype)[..., :self.true_n]
        return y

    def tree_flatten(self):
        return (self.base,), (self.axis, self.true_n)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1])


def _wrap_collectives(p, axis: str, true_vocab: int):
    """Inject the TP collectives into the param pytree: row-parallel
    projections all-reduce, the col-sharded lm_head all-gathers."""
    layers = dict(p["layers"])
    for name in ("o_proj", "down_proj", "experts_down"):
        if name in layers:
            layers[name] = AllReduceLinear(layers[name], axis)
    out = {**p, "layers": layers}
    if "lm_head" in out:
        out["lm_head"] = AllGatherLinear(out["lm_head"], axis, true_vocab)
    return out


def _local_forward(cfg_l, axis: str, true_vocab: int):
    """Per-device forward over local head/column shards: the REAL
    generalized decoder (M.forward) — every family knob by construction
    — with collectives injected through the weight leaves."""

    def fwd(p, tokens, ck, cv, pos):
        p = _wrap_collectives(_localize_qtensors(p), axis, true_vocab)
        cache = KVCache(ck, cv, pos)
        lg, cache2 = M.forward(p, cfg_l, tokens, cache, last_only=True)
        return lg[:, -1], cache2.k, cache2.v

    return fwd


@functools.lru_cache(maxsize=32)
def _tp_fn(cfg, mesh, axis):
    n = mesh.shape[axis]
    cfg_l = _tp_cfg(cfg, n, axis)
    fwd = _local_forward(cfg_l, axis, cfg.vocab_size)

    # param specs must match how shard_params_tp laid them out; the spec
    # pytree uses the PARAM SHAPE tree, built lazily at first call
    def run(params, tokens, cache):
        pspecs = tp_param_specs(params, mesh, axis=axis)
        f = jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(pspecs, P(), tp_cache_specs(axis),
                      tp_cache_specs(axis),
                      P()),
            out_specs=(P(), tp_cache_specs(axis), tp_cache_specs(axis)),
            check_vma=False)
        lg, ck, cv = f(params, tokens, cache.k, cache.v, cache.pos)
        return lg, KVCache(ck, cv, cache.pos + tokens.shape[1])

    return tracked_jit("tp_forward_step", run, donate_argnums=(2,))


def tp_forward_step(
    params: Dict[str, Any],
    cfg,
    tokens: jax.Array,        # [B, Sq] int32
    cache: KVCache,
    mesh: Mesh,
    axis: str = "tp",
) -> Tuple[jax.Array, KVCache]:
    """One prefill/decode step (last-position logits [B, V], cache).
    Params/cache must be laid out by shard_params_tp/new_cache_tp."""
    fn = _tp_fn(cfg, mesh, axis)
    return fn(params, jnp.asarray(tokens, jnp.int32), cache)


def tp_generate(
    params: Dict[str, Any],
    cfg,
    input_ids,
    mesh: Mesh,
    axis: str = "tp",
    max_new_tokens: int = 32,
    max_seq: int = 2048,
    eos_token_id: Optional[int] = None,
) -> np.ndarray:
    """Greedy explicit-TP generation -> [B, S + new]."""
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    b, s = ids.shape
    if s + max_new_tokens > max_seq:
        raise ValueError("prompt + max_new_tokens exceeds max_seq")
    cache = new_cache_tp(cfg, b, max_seq, mesh, axis=axis)
    lg, cache = tp_forward_step(params, cfg, jnp.asarray(ids), cache,
                                mesh, axis)
    out = [np.asarray(jnp.argmax(lg, axis=-1), np.int32)]
    for _ in range(max_new_tokens - 1):
        tok = jnp.asarray(out[-1][:, None])
        lg, cache = tp_forward_step(params, cfg, tok, cache, mesh, axis)
        nxt = np.asarray(jnp.argmax(lg, axis=-1), np.int32)
        out.append(nxt)
        if eos_token_id is not None and (nxt == eos_token_id).all():
            break
    return np.concatenate([ids, np.stack(out, axis=1)], axis=1)
