"""Child phases of ``chip_smoke.py`` (repo root): the parts of the smoke
that have to touch JAX, each run as its own process.

A chip belongs to one process at a time, so the smoke's parent stays
off JAX and every phase here has exited before the next one starts:

    python -m bigdl_tpu.smoke build --seed S --out DIR   # model -> low-bit dir
    python -m bigdl_tpu.smoke qlora --seed S --out DIR   # 5 QLoRA steps
    python -m bigdl_tpu.smoke tp    --seed S             # 4-chip explicit TP

(the two serve phases are the real ``bigdl_tpu.serving.api_server`` entry
point, driven over HTTP by the parent). Each phase prints ONE JSON
object as its last stdout line — its assertions under ``checks`` (name
-> bool), ``ok`` = all of them — and exits non-zero unless ``ok``.

The model is Mistral-7B-v0.1 at its published config, weights random
from ``--seed``, built the way a deployment's load builds it: hf_config
dict -> registry family -> config_from_hf -> merge -> ``TpuCausalLM``
(prepack) -> ``save_low_bit``. ``--tiny`` swaps in 2-layer toy widths
for CPU rehearsals and tests; it never makes a phase accept a non-TPU
device at full size.
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time

# Mistral-7B-v0.1 config.json as published (mistralai/Mistral-7B-v0.1).
MISTRAL_7B_HF = {
    "architectures": ["MistralForCausalLM"],
    "model_type": "mistral",
    "vocab_size": 32000,
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "hidden_act": "silu",
    "max_position_embeddings": 32768,
    "rms_norm_eps": 1e-5,
    "rope_theta": 10000.0,
    "sliding_window": 4096,
    "tie_word_embeddings": False,
    "bos_token_id": 1,
    "eos_token_id": 2,
    "torch_dtype": "bfloat16",
}

# utils/testing.TINY_LLAMA widths under the same architecture name
TINY_HF = dict(MISTRAL_7B_HF, vocab_size=256, hidden_size=64,
               intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=8, num_key_value_heads=4,
               max_position_embeddings=256)
# explicit TP needs every quantized plane to split four ways
# (K/32 % 4 == 0): the tests/test_tp.py widths
TINY_TP_HF = dict(TINY_HF, hidden_size=256, intermediate_size=512)

QLORA_STEPS = 5
TP_WAYS = 4
TP_NEW_TOKENS = 16


def tp_tolerance(layers: int, ways: int) -> float:
    """bf16 budget for the TP logits against one chip, as a share of the
    reference's L2 norm. One chip accumulates each matmul in f32 and
    rounds once; under TP the two row-parallel matmuls of a layer (o,
    down) each round `ways` partial sums to bf16 and their sum once
    more — up to 2**-8 relative apiece — and the errors walk randomly
    through the residual stream: 2**-8 * sqrt(2 * layers * (ways + 1)),
    0.07 for 32 layers four ways. (A first guess of 0.05, scaled up
    from a CPU run at hidden 512, was too tight: the chip measured
    0.057 with all 16 greedy tokens equal.)"""
    return 2.0 ** -8 * math.sqrt(2 * layers * (ways + 1))


def hf_config(tiny: bool, tp: bool = False) -> dict:
    if not tiny:
        return dict(MISTRAL_7B_HF)
    return dict(TINY_TP_HF if tp else TINY_HF)


def max_seq_for(tiny: bool) -> int:
    """Serving/cache length of the smoke. <= 2048 at full size: the
    published 4096-token sliding window then masks nothing (the kernels
    implement no window; beyond it is ROADMAP R3)."""
    return 256 if tiny else 2048


def _device_block():
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _finish(phase: str, t0: float, checks: dict, **fields) -> int:
    ok = all(bool(v) for v in checks.values())
    print(json.dumps({"phase": phase, "ok": ok, "checks": checks,
                      "wall_s": round(time.time() - t0, 2), **fields}),
          flush=True)
    return 0 if ok else 1


def _require_device(phase: str, t0: float, tiny: bool, need: int = 1):
    """The device block, after refusing anything but a TPU at full size
    (BEFORE a 7B model is built on a CPU)."""
    dev = _device_block()
    if not tiny and dev["platform"] != "tpu":
        sys.exit(_finish(phase, t0, {"device_is_tpu": False}, device=dev))
    if dev["count"] < need:
        sys.exit(_finish(phase, t0, {f"has_{need}_devices": False},
                         device=dev))
    return dev


def _build_model(hf: dict, seed: int, max_seq: int, merge: bool):
    """hf_config dict -> registry family -> config -> seeded random
    sym_int4 params (-> merged projections) -> TpuCausalLM (prepack)."""
    from bigdl_tpu.models import llama as llama_mod
    from bigdl_tpu.models.registry import get_family
    from bigdl_tpu.transformers.model import TpuCausalLM
    from bigdl_tpu.utils.testing import random_llama_params

    family = get_family(hf["architectures"][0], hf)
    cfg = family.config_from_hf(hf)
    params = random_llama_params(cfg, qtype="sym_int4", seed=seed)
    if merge:
        params = llama_mod.merge_projections(params, cfg)
    return TpuCausalLM(params, cfg, family, hf, qtype="sym_int4",
                       max_seq=max_seq)


def _memory_stats() -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use",
                                       "bytes_limit") if k in stats}


def phase_build(args) -> int:
    t0 = time.time()
    import jax
    from jax._src import xla_bridge

    # what a launcher that only IMPORTS the package does to the chip:
    # the router parent imports bigdl_tpu.* and must not take the device
    # its replicas need (ISSUE 21 item 3)
    import bigdl_tpu.serving.api_server  # noqa: F401
    import bigdl_tpu.serving.router  # noqa: F401

    import_took_backend = xla_bridge.backends_are_initialized()
    dev = _require_device("build", t0, args.tiny)

    from bigdl_tpu.config import enable_compilation_cache, target_is_tpu
    from bigdl_tpu.observability.compile_watch import compile_table

    enable_compilation_cache()
    hf = hf_config(args.tiny)
    t_load = time.time()
    model = _build_model(hf, args.seed, max_seq_for(args.tiny), merge=True)
    jax.block_until_ready(model.params)
    load_s = time.time() - t_load
    rep = dict(model.prepack_report)
    layers = model.params["layers"]
    checks = {
        "import_leaves_backend_alone": not import_took_backend,
        "projections_merged": "qkv_proj" in layers
        and "gate_up_proj" in layers,
    }
    if target_is_tpu():
        import jax.numpy as jnp

        # the shipped relayout actually happened, on every quantized leaf
        checks["prepack_applied"] = bool(rep["applied"])
        checks["prepack_all_converted"] = \
            rep["converted"] == rep["qtensors"] > 0
        checks["weights_are_int4_dtype"] = \
            layers["qkv_proj"].data.dtype == jnp.int4
        checks["relayout_compiled"] = \
            compile_table().get("int4_mxu_relayout", {}).get(
                "compiles", 0) > 0
    t_save = time.time()
    model.save_low_bit(args.out)
    save_s = time.time() - t_save
    return _finish("build", t0, checks, device=dev, model=hf["model_type"],
                   layers=hf["num_hidden_layers"], prepack=rep,
                   build_s=round(load_s, 2), save_s=round(save_s, 2),
                   memory=_memory_stats(), low_bit_dir=args.out)


def phase_qlora(args) -> int:
    t0 = time.time()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    dev = _require_device("qlora", t0, args.tiny)

    from bigdl_tpu.config import enable_compilation_cache
    from bigdl_tpu.observability.compile_watch import (compile_table,
                                                       tracked_jit)
    from bigdl_tpu.ops.quant import QTensor
    from bigdl_tpu.qlora import (LoraConfig, attach_lora,
                                 lora_trainable_mask)
    from bigdl_tpu.training import make_lora_train_step, partition
    from bigdl_tpu.transformers.model import AutoModelForCausalLM

    enable_compilation_cache()
    t_load = time.time()
    # split projection layout: the LoRA targets name q_proj/k_proj/...
    model = AutoModelForCausalLM.from_pretrained(
        args.out, merge_projections=False)
    load_s = time.time() - t_load
    cfg = model.config
    params = attach_lora(model.params,
                         LoraConfig(r=16, training_mode="qlora"))
    train, frozen = partition(params, lora_trainable_mask(params))
    optimizer = optax.adamw(2e-4)
    step = make_lora_train_step(model.family.forward_train, cfg, optimizer)
    opt_state = optimizer.init(train)

    # the reference alpaca-qlora recipe: micro-batch 8, cutoff_len 256
    batch, seq = (2, 32) if args.tiny else (8, 256)
    rng = np.random.default_rng(args.seed)
    data = {
        "input_ids": jnp.asarray(
            rng.integers(1, cfg.vocab_size, (batch, seq)), jnp.int32),
        "attention_mask": jnp.ones((batch, seq), jnp.int32),
    }

    planes = [plane
              for leaf in jax.tree_util.tree_leaves(
                  frozen, is_leaf=lambda x: isinstance(x, QTensor))
              if isinstance(leaf, QTensor)
              for plane in (leaf.data, leaf.scale)]

    @functools.partial(tracked_jit, "smoke_frozen_digest")
    def digest(planes):
        # one wrapping int32 sum of each plane's raw bits; jitted so the
        # widening fuses into the reduction (no full-size int32 copy of
        # a stacked 7B leaf)
        def one(plane):
            if jnp.issubdtype(plane.dtype, jnp.integer):
                bits = plane.astype(jnp.int32)
            else:
                bits = jax.lax.bitcast_convert_type(
                    plane.astype(jnp.float32), jnp.int32)
            return jnp.sum(bits, dtype=jnp.int32)

        return jnp.stack([one(p) for p in planes])

    def frozen_digest():
        """Every frozen QTensor's code and scale plane, each reduced on
        device to one int32."""
        return np.asarray(digest(planes)).tolist()

    before = frozen_digest()
    losses, step_s = [], []
    for _ in range(QLORA_STEPS):
        t = time.time()
        train, opt_state, loss = step(train, opt_state, frozen, data)
        losses.append(float(jax.block_until_ready(loss)))
        step_s.append(round(time.time() - t, 3))
    after = frozen_digest()
    compiles = compile_table().get("lora_train_step", {}).get("compiles", 0)
    checks = {
        "loss_finite_every_step": all(math.isfinite(x) for x in losses),
        "loss_fell": losses[-1] < losses[0],
        "frozen_base_bit_identical": bool(before) and before == after,
        "one_compile_of_the_step": compiles == 1,
    }
    return _finish("qlora", t0, checks, device=dev, losses=losses,
                   step_s_smoke_timing=step_s, batch=batch, seq=seq,
                   lora_rank=16, load_s=round(load_s, 2),
                   memory=_memory_stats())


def phase_tp(args) -> int:
    t0 = time.time()
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = _require_device("tp", t0, args.tiny, need=TP_WAYS)

    from bigdl_tpu.config import enable_compilation_cache
    from bigdl_tpu.observability.compile_watch import tracked_jit
    from bigdl_tpu.parallel import make_mesh
    from bigdl_tpu.parallel.tp import (_tp_fn, new_cache_tp,
                                       shard_params_tp, tp_generate)

    enable_compilation_cache()
    hf = hf_config(args.tiny, tp=True)
    max_seq = max_seq_for(args.tiny)
    # explicit TP shards the SPLIT projection layout
    model = _build_model(hf, args.seed, max_seq, merge=False)
    cfg, family = model.config, model.family
    plen = 32 if args.tiny else 128
    ids = np.random.default_rng(args.seed).integers(
        1, cfg.vocab_size, (1, plen)).astype(np.int32)

    # one chip: the user entry point, then first-step logits to compare
    ref_out = model.generate(ids, max_new_tokens=TP_NEW_TOKENS)
    ref_lg, _ = tracked_jit("smoke_ref_prefill", family.prefill,
                            static_argnums=1)(
        model.params, cfg, jnp.asarray(ids),
        family.new_cache(cfg, 1, max_seq))
    ref_lg = np.asarray(ref_lg, np.float32).reshape(-1)

    mesh = make_mesh(devices=jax.devices()[:TP_WAYS], tp=TP_WAYS)
    with mesh:
        params = shard_params_tp(model.params, mesh)
        jax.block_until_ready(params)
        del model
        gc.collect()
        per_dev = [(d.memory_stats() or {}).get("bytes_in_use")
                   for d in jax.devices()[:TP_WAYS]]
        leaves = jax.tree_util.tree_leaves(params)
        cache = new_cache_tp(cfg, 1, max_seq, mesh)
        # compile the step once ahead of time to read its text; the
        # jitted call below then finds it in the compilation cache
        fn = _tp_fn(cfg, mesh, "tp")
        text = fn.lower(params, jnp.asarray(ids), cache).compile().as_text()
        lg, cache = fn(params, jnp.asarray(ids), cache)
        lg = np.asarray(lg, np.float32).reshape(-1)
        out = tp_generate(params, cfg, ids, mesh,
                          max_new_tokens=TP_NEW_TOKENS, max_seq=max_seq)
    rel = float(np.linalg.norm(lg - ref_lg)
                / max(np.linalg.norm(ref_lg), 1e-30))
    tol = tp_tolerance(cfg.num_hidden_layers, TP_WAYS)
    checks = {
        "logits_finite": bool(np.isfinite(lg).all()),
        "logits_within_bf16_tolerance": rel <= tol,
        "every_leaf_on_4_devices": all(
            len(x.sharding.device_set) == TP_WAYS for x in leaves),
        "all_reduce_in_compiled_text": "all-reduce" in text,
        "generated_16_tokens": out.shape == (1, plen + TP_NEW_TOKENS)
        and ref_out.shape == out.shape,
    }
    if dev["platform"] == "tpu":
        checks["pallas_kernel_on_shards"] = "tpu_custom_call" in text
        checks["bytes_per_device_within_1.5x"] = (
            None not in per_dev and max(per_dev) < 1.5 * min(per_dev))
    return _finish("tp", t0, checks, device=dev,
                   logits_rel_l2=round(rel, 5), tolerance=round(tol, 5),
                   greedy_tokens_agree=int(
                       (out[0, plen:] == ref_out[0, plen:]).sum()),
                   bytes_in_use_per_device=per_dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("phase", choices=["build", "qlora", "tp"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="low-bit model directory (build writes it, "
                         "qlora loads it)")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    if args.phase != "tp" and not args.out:
        ap.error("--out is required")
    return {"build": phase_build, "qlora": phase_qlora,
            "tp": phase_tp}[args.phase](args)


if __name__ == "__main__":
    sys.exit(main())
