"""Runner of the training cells (traffic kind ``train``): one QLoRA
step after another in this process, on one fixed micro-batch of seeded
tokens.

The window opens after the warm-up steps and closes at the first step
boundary at or after ``--seconds``; the rate is every token of every
step that finished in it over its whole length (a window cut in the
middle of a step of seconds would count 15 or 16 steps by chance).

The configuration's ``reference``, ``weights`` and ``costs`` modules
come from the cell (``cell.modules``); none is imported here by name.
"""

from __future__ import annotations

import math
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

from harness import common, traffic as traffic_mod

STEP_SPAN = "train_step"
WARMUP_STEPS = 2
SAMPLE_TOKENS = 128
SAMPLE_LOSS_LIMIT = 0.01        # of the reference's loss


def run(cell, *, seed: int, seconds: float, trace: bool, tiny: bool,
        t_process: float, out_dir: Path, device: Dict[str, Any],
        peaks: Optional[Dict[str, float]]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from bigdl_tpu.ops.quant import QTensor
    from bigdl_tpu.qlora import (LoraConfig, attach_lora,
                                 lora_trainable_mask)
    from bigdl_tpu.training import make_lora_train_step, partition

    watch = common.CompileWatch().install()
    clock = common.WallClock(t_process)
    clock.lap("devices_ready")
    config, traffic = cell.config, cell.traffic
    reference, weights, costs = (cell.modules[k] for k in (
        "reference", "weights", "costs"))
    tcfg = config["train"]
    dims = costs.Dims.from_config(config)
    batch_np = traffic_mod.train_batch(traffic, seed, dims.vocab_size)
    sample = [int(x) for x in batch_np["input_ids"][0][:SAMPLE_TOKENS]]
    quant = {"qtype": config["quant"], "block": config["quant_block"]}

    ref_box: Dict[str, Any] = {}
    model, build_stages = weights.build_model(
        config, seed, merge=bool(tcfg.get("merge_projections", False)),
        with_canonical=lambda canonical, cfg: ref_box.update(
            logits=np.asarray(reference.all_logits(
                canonical, config["reference"], quant, sample))))
    clock.lap("weights")
    # the reference's pass over the sample ran inside ``build_model``
    clock.move(build_stages.get("with_canonical_s", 0.0), "weights",
               "check_a_reference")
    cfg = model.config
    params = attach_lora(model.params, LoraConfig(
        r=int(tcfg["lora_r"]), training_mode=tcfg["training_mode"]))
    # the program's training forward on the sample, before any step:
    # the adapters start at zero, so it must equal the reference's base
    fwd = jax.jit(model.family.forward_train, static_argnums=1)
    prog = np.asarray(fwd(params, cfg, jnp.asarray([sample], jnp.int32)),
                      np.float32)[0]
    rel = common.relative_l2(prog, ref_box["logits"])
    tol = reference.tolerance(config, "bf16")
    ref_loss = common.next_token_loss(ref_box["logits"], sample)
    prog_loss = common.next_token_loss(prog, sample)
    del prog, ref_box, fwd
    clock.lap("check_a_program")

    train, frozen = partition(params, lora_trainable_mask(params))
    if tcfg["optimizer"] != "adamw":
        raise ValueError(f"optimizer {tcfg['optimizer']!r} is not wired")
    optimizer = optax.adamw(float(tcfg["learning_rate"]))
    step = make_lora_train_step(model.family.forward_train, cfg, optimizer)
    opt_state = optimizer.init(train)
    data = {k: jnp.asarray(v) for k, v in batch_np.items()}
    tokens_per_step = int(batch_np["input_ids"].size)

    planes = [plane
              for leaf in jax.tree_util.tree_leaves(
                  frozen, is_leaf=lambda x: isinstance(x, QTensor))
              if isinstance(leaf, QTensor)
              for plane in (leaf.data, leaf.scale)]

    @jax.jit
    def digest(planes):
        def one(plane):
            if jnp.issubdtype(plane.dtype, jnp.integer):
                bits = plane.astype(jnp.int32)
            else:
                bits = jax.lax.bitcast_convert_type(
                    plane.astype(jnp.float32), jnp.int32)
            return jnp.sum(bits, dtype=jnp.int32)

        return jnp.stack([one(p) for p in planes])

    def one_step():
        nonlocal train, opt_state
        if trace:
            with jax.profiler.TraceAnnotation(STEP_SPAN):
                train, opt_state, loss = step(train, opt_state, frozen,
                                              data)
                return float(jax.block_until_ready(loss))
        train, opt_state, loss = step(train, opt_state, frozen, data)
        return float(jax.block_until_ready(loss))

    losses: List[float] = []
    for _ in range(WARMUP_STEPS):
        losses.append(one_step())
    before = np.asarray(digest(planes)).tolist()
    c_setup = watch.snapshot()
    clock.lap("warmup")

    t0 = time.monotonic()
    setup_s = t0 - t_process
    w0 = watch.snapshot()
    steps = 0
    trace_dir = out_dir / "trace"
    tracing = False
    traced = False
    tr_start = min(float(traffic.get("trace_start_s", 3.0)), seconds * 0.3)
    tr_len = min(float(traffic.get("trace_seconds", 3.0)), seconds * 0.4)
    t_trace = 0.0
    while True:
        now = time.monotonic()
        if now - t0 >= seconds:
            break
        if trace and not traced and not tracing and now - t0 >= tr_start:
            common.start_trace(trace_dir)
            tracing, t_trace = True, time.monotonic()
        losses.append(one_step())
        steps += 1
        if tracing and time.monotonic() - t_trace >= tr_len:
            jax.profiler.stop_trace()
            tracing, traced = False, True
    if tracing:
        jax.profiler.stop_trace()
        traced = True
    window_s = time.monotonic() - t0
    w1 = watch.snapshot()
    after = np.asarray(digest(planes)).tolist()
    mem_peak = common.memory_peak_bytes()
    clock.lap("window")

    jax_compiles = watch.programs_between(w0, w1)
    checks = {
        "loss_finite_every_step": all(math.isfinite(x) for x in losses),
        "loss_fell": losses[-1] < losses[0],
        "frozen_base_bit_identical": bool(before) and before == after,
        "no_compile_in_window": jax_compiles == 0,
        "reference_within_tolerance": rel <= tol,
        "sample_loss_matches_reference":
            abs(prog_loss - ref_loss) <= SAMPLE_LOSS_LIMIT * abs(ref_loss),
    }
    values = {"train_tokens_per_s": steps * tokens_per_step / window_s,
              "setup_s": setup_s}
    dev = dict(device)
    dev["memory_peak_bytes"] = mem_peak
    result: Dict[str, Any] = {
        "correct": all(checks.values()), "attempted": steps, "failed": 0,
        "device": dev}
    if not trace:
        result["metrics"] = common.select_end_to_end(
            cell, {} if tiny else values)
    else:
        obs = {"counters_start": None, "counters_end": None,
               "memory_peak_bytes": mem_peak or None,
               "device_kind": device["kind"] if not tiny else None,
               "peaks": peaks,
               "work": costs.training_work(config, dims, traffic,
                                           tokens_per_step)}
        common.traced_metrics(cell, result, obs,
                              trace_dir if traced else None, STEP_SPAN,
                              tiny, out_dir)
        clock.lap("trace_reduction")

    wall = clock.close()
    common.note(
        info="run", workload=cell.name, seed=seed, seconds=seconds,
        window_s=window_s, steps=steps, tokens_per_step=tokens_per_step,
        checks=checks, reference_rel_l2=rel, reference_tolerance=tol,
        sample_loss={"program": prog_loss, "reference": ref_loss},
        losses=[losses[0], losses[WARMUP_STEPS], losses[-1]],
        train_tokens_per_s=values["train_tokens_per_s"],
        wall_s=wall["wall_s"], budget_s=common.RUN_BUDGET_S,
        phases=wall["phases"],
        setup={"setup_s": setup_s,
               "build_stages": build_stages,
               "backend_compiles": c_setup["backend_compiles"],
               "cache_hits": c_setup["cache_hits"],
               "backend_compile_s": c_setup["backend_seconds"]},
        window_compiles={"jax": jax_compiles})

    result["compared"] = common.report_compared(
        [("reference_rel_l2", rel, tol),
         ("sample_loss_gap", abs(prog_loss - ref_loss),
          SAMPLE_LOSS_LIMIT * abs(ref_loss))], checks)
    return result
